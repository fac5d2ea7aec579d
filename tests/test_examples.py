"""Smoke-run the example scripts on the CPU mesh (the reference's
example benchmarks double as its multi-node validation, SURVEY.md §4).
Each runs in-process with tiny step counts."""

import sys

import pytest
import runpy


def _run(path, *argv):
    old = sys.argv
    sys.argv = [path, *argv]
    try:
        runpy.run_path(path, run_name="__main__")
    finally:
        sys.argv = old


@pytest.mark.parametrize("path,argv", [
    ("example/jax/train_mnist_mlp.py", ("--steps", "2", "--batch", "2")),
    ("example/jax/benchmark_bert.py",
     ("--tiny", "--steps", "1", "--batch", "1")),
    ("example/jax/benchmark_resnet.py",
     ("--model", "tiny", "--batch", "1", "--size", "16", "--steps", "1")),
    ("example/jax/train_llama.py",
     ("--steps", "8", "--batch", "8", "--seq", "16")),
    ("example/jax/train_parallel_axes.py",
     ("--mode", "tp", "--steps", "2", "--batch", "8", "--seq", "16")),
    ("example/jax/train_parallel_axes.py",
     ("--mode", "pp", "--steps", "2", "--batch", "8", "--seq", "16",
      "--microbatches", "2")),
    ("example/jax/train_parallel_axes.py",
     ("--mode", "ep", "--steps", "2", "--batch", "4", "--experts", "8")),
    ("example/jax/train_parallel_axes.py",
     ("--mode", "zero", "--steps", "2", "--batch", "8", "--seq", "16")),
    ("example/jax/train_parallel_axes.py",
     ("--mode", "fsdp", "--steps", "2", "--batch", "8", "--seq", "16")),
    ("example/jax/train_parallel_axes.py",
     ("--mode", "3d", "--steps", "2", "--batch", "8", "--seq", "16",
      "--microbatches", "2")),
    ("example/jax/train_long_context.py",
     ("--steps", "2", "--seq", "128", "--sp", "4", "--tiny",
      "--batch", "4")),
    ("example/jax/train_long_context.py",
     ("--steps", "2", "--seq", "128", "--sp", "4", "--tiny",
      "--batch", "4", "--attention", "ring_flash")),
    ("example/jax/train_long_context.py",
     ("--steps", "2", "--seq", "128", "--sp", "4", "--tiny",
      "--batch", "4", "--attention", "ulysses_flash")),
    ("example/pytorch/train_mnist_byteps.py", ("--steps", "2")),
    ("example/pytorch/benchmark_byteps.py",
     ("--num-iters", "1", "--num-tensors", "2", "--tensor-mb", "0.1")),
    ("example/pytorch/benchmark_byteps_ddp.py",
     ("--num-iters", "1", "--accumulate", "2", "--batch", "4")),
    ("example/pytorch/benchmark_cross_barrier_byteps.py",
     ("--num-iters", "2", "--batch", "4")),
    ("example/pytorch/elastic_benchmark_byteps.py", ()),
])
def test_example_smoke(path, argv):
    _run(path, *argv)


@pytest.mark.parametrize("path,argv", [
    ("example/tensorflow/tensorflow2_mnist.py", ("--steps", "2")),
    ("example/tensorflow/synthetic_benchmark_tf2.py",
     ("--num-iters", "1", "--num-tensors", "1", "--tensor-mb", "0.1")),
    ("example/tensorflow/tensorflow2_mnist_bps_MirroredStrategy.py",
     ("--steps", "2",)),
    ("example/keras/keras_mnist.py", ("--epochs", "1", "--batch", "256")),
])
def test_tf_example_smoke(path, argv):
    pytest.importorskip("tensorflow")
    _run(path, *argv)

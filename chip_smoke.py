"""chip_smoke.py — does the push_pull main path still start on the chip?

One process drives the system through its public entry points on every
device JAX reports, cheapest phase first, and checks each result by the
repo's own means.  It is the quickest proof that the system runs on a
TPU; it is NOT a benchmark — the seconds it prints are observations and
nothing compares them.

    python chip_smoke.py                  # the chip; fails without a TPU
    python chip_smoke.py --rehearsal      # tiny CPU run of the control flow

Phases (each prints one JSON line):

  device        every device is a TPU; versions; compile-cache directory
  engine        bps.init, a 64 MB/rank rank-stacked tensor through
                push_pull and push_pull_async/synchronize == numpy mean;
                the same with onebit compression vs tests/compression_refs
  fused_train   BERT-large, seq 128, 32 examples/chip, adamw, 5 steps of
                make_dp_train_step; loss finite and falling; on several
                chips its all-reduce is asynchronous (collective_schedule)
  engine_train  same model/batch/params through engine-mode
                DistributedOptimizer, 3 steps; losses agree with fused
  kernels       flash_attention fwd + grad vs exact attention; onebit
                kernels vs numpy refs; Mosaic custom call in compiled text
  dcn           (>= 4 devices) re-init as a (2, n/2) mesh, 2 fused steps
                with the onebit-compressed DCN hop

The last stdout line is one JSON object: on the chip
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
With no TPU it exits non-zero and prints no result; there is no
automatic CPU path — the rehearsal is an explicit argument, runs a tiny
model, and labels its output ``"device": "cpu", "rehearsal": true``.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import sys
import threading
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

PHASES = ("device", "engine", "fused_train", "engine_train", "kernels", "dcn")
# Seconds a phase may take on ONE chip before the watchdog fails it BY
# NAME and exits (a wedged collective never returns from
# block_until_ready, so the observation has to come from another thread).
# Cold compiles included; doubled on several chips, where every program
# is an SPMD compile.  Observed cold on one v5e: 14 / 97 / 76 / 51 s.
PHASE_BUDGET_S = {"device": 120, "engine": 300, "fused_train": 420,
                  "engine_train": 480, "kernels": 240, "dcn": 420}
MOSAIC_CALL = "tpu_custom_call"
# asynchronous all-reduces of BERT-large's DP step on several chips: 20
# when parallel.data_parallel's options engage, 2 when they do not
MIN_ASYNC_REDUCES = 10


def emit(doc: dict) -> None:
    print(json.dumps(doc), flush=True)


class Watchdog:
    """Fails the running phase by name instead of hanging: past its
    budget, dump every thread's stack and exit non-zero."""

    def __init__(self):
        self._lock = threading.Lock()
        self._phase = None
        self._budget = self._deadline = 0.0
        threading.Thread(target=self._run, name="smoke-watchdog",
                         daemon=True).start()

    def arm(self, phase: str, budget_s: float) -> None:
        with self._lock:
            self._phase, self._budget = phase, budget_s
            self._deadline = time.monotonic() + budget_s

    def disarm(self) -> None:
        with self._lock:
            self._phase = None

    def _run(self) -> None:
        while True:
            time.sleep(1.0)
            with self._lock:
                phase, late = self._phase, time.monotonic() > self._deadline
                budget = self._budget
            if phase is not None and late:
                emit({"phase": phase, "pass": False,
                      "error": f"timeout: phase exceeded its "
                               f"{budget:.0f}s budget"})
                faulthandler.dump_traceback(file=sys.stderr)
                emit({"ok": False, "failed": [phase]})
                os._exit(3)


def peak_bytes(devices) -> list:
    """peak_bytes_in_use per device (process-lifetime high-water mark;
    None where the backend does not report it, i.e. CPU)."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        out.append(stats.get("peak_bytes_in_use") if stats else None)
    return out


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def sum_order_rtol(n_terms: int, reductions: int) -> float:
    """Relative tolerance between two float32 sums of ``n_terms`` taken in
    different orders (numpy pairwise vs the chip's tiled tree): each is
    within ~log2(n)·eps/2 of the true sum, so they agree to log2(n)·eps;
    ``reductions`` is how many such sums the compared value went through.
    The onebit SIGNS are compared exactly — only the scale rides this."""
    import numpy as np
    return float(reductions * np.log2(max(2, n_terms))
                 * np.finfo(np.float32).eps)


# --------------------------------------------------------------------------
# sizes: the chip runs the real widths; the rehearsal is a labelled toy
# --------------------------------------------------------------------------

def sizes(rehearsal: bool) -> dict:
    if rehearsal:
        return dict(engine_elems=96 * 1024, partition_bytes=65536,
                    seq=32, per_chip=2,
                    flash=[(1, 256, 2, 128), (2, 128, 2, 64)])
    return dict(engine_elems=16 * 1024 * 1024, partition_bytes=None,
                seq=128, per_chip=32,
                flash=[(4, 4096, 16, 128), (8, 128, 16, 64)])


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def phase_device(ctx) -> dict:
    import jax
    import jaxlib
    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "n_devices": len(devices),
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "compile_cache_dir": ctx["cache_dir"]}
    try:
        from importlib.metadata import version
        info["libtpu"] = version("libtpu")
    except Exception:  # noqa: BLE001 — version string is informational
        info["libtpu"] = None
    if not ctx["rehearsal"]:
        check(all(d.platform == "tpu" for d in devices),
              f"not every device is a TPU: "
              f"{[d.platform for d in devices]}")
    return info


def _onebit_reference(x, bounds, refs, np):
    """numpy replay of the engine's compressed push_pull (op=average) per
    chunk: every rank packs its chunk, the packed payloads are decoded
    and summed in rank order, the sum is re-packed ("server") and
    decoded, then divided by the rank count.  Returns (values, the
    server-side packed words per chunk)."""
    R = x.shape[0]
    out = np.empty(x.shape[1], np.float32)
    words = []
    for off, ln in bounds:
        y = np.zeros(ln, np.float32)
        for r in range(R):
            w, s = refs.onebit_compress(x[r, off:off + ln])
            y = y + refs.onebit_decompress(w, s, ln)
        w2, s2 = refs.onebit_compress(y)
        words.append(w2)
        out[off:off + ln] = refs.onebit_decompress(w2, s2, ln) / np.float32(R)
    return out, words


def phase_engine(ctx) -> dict:
    import jax
    import numpy as np

    import byteps_tpu as bps
    from byteps_tpu.comm.mesh import get_comm
    from byteps_tpu.common.config import Config, get_config
    from byteps_tpu.common.partitioner import chunk_bounds
    from tests import compression_refs as refs
    from tools._bench_util import metrics_diag

    sz, n = ctx["sizes"], ctx["n"]
    if sz["partition_bytes"]:
        bps.init(Config(partition_bytes=sz["partition_bytes"]))
    else:
        bps.init()
    comm = get_comm()
    check(comm.n_dcn == 1 and comm.n_ici == n and bps.size() == n,
          f"mesh is (dcn={comm.n_dcn}, ici={comm.n_ici}), size "
          f"{bps.size()}; expected (1, {n})")
    N = sz["engine_elems"]
    part = get_config().partition_bytes
    obs = {"mesh": [comm.n_dcn, comm.n_ici], "elems_per_rank": N,
           "partition_bytes": part}

    # a different value per rank, with per-rank magnitudes whose signed
    # sums stay far from zero (the merged onebit sign must not hinge on
    # the last bit of a scale)
    rng = np.random.RandomState(21)
    mags = np.asarray([1.0, 1.37, 1.93, 2.71, 3.3, 4.1, 5.3, 6.7],
                      np.float32)
    x = (rng.standard_normal((n, N)).astype(np.float32)
         * mags[np.arange(n) % len(mags), None])
    want = x.mean(axis=0, dtype=np.float64).astype(np.float32)

    # --- uncompressed: sync from host rows, async from chip-resident rows
    bps.declare("smoke/dense", shape=(N,), dtype="float32", local=False)
    t0 = time.perf_counter()
    out = np.asarray(bps.push_pull(x, "smoke/dense"))
    obs["dense_first_s"] = round(time.perf_counter() - t0, 3)
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)
    xd = jax.device_put(x, comm.stacked_sharding(extra_dims=1))
    check(len(xd.sharding.device_set) == n,
          "rank-stacked input does not span every device")
    t0 = time.perf_counter()
    h = bps.push_pull_async(xd, "smoke/dense")
    out2 = np.asarray(bps.synchronize(h, timeout=120.0))
    obs["dense_second_s"] = round(time.perf_counter() - t0, 3)
    np.testing.assert_allclose(out2, want, rtol=1e-6, atol=1e-6)
    obs["dense_chunks"] = len(chunk_bounds(N, 4, part))
    check(ctx["rehearsal"] or obs["dense_chunks"] >= 8,
          f"only {obs['dense_chunks']} chunk(s): not the chunked path")

    # --- onebit: packed signs equal; decoded magnitudes agree to float32
    # summation order over two reductions (per-rank scale, merged scale)
    ckw = {"compressor": "onebit"}
    bps.declare("smoke/onebit", shape=(N,), dtype="float32",
                compression=ckw)
    t0 = time.perf_counter()
    h = bps.push_pull_async(xd, "smoke/onebit", compression=ckw)
    got = np.asarray(bps.synchronize(h, timeout=120.0))
    obs["onebit_first_s"] = round(time.perf_counter() - t0, 3)
    bounds = chunk_bounds(N, 4, part)
    ref_vals, ref_words = _onebit_reference(x, bounds, refs, np)
    for (off, ln), w in zip(bounds, ref_words):
        got_words, _ = refs.onebit_compress(got[off:off + ln])
        check(np.array_equal(got_words, w),
              f"onebit sign words differ from the numpy reference in "
              f"chunk at {off}")
    rtol = sum_order_rtol(max(ln for _, ln in bounds), reductions=2)
    rel = np.abs(got - ref_vals) / np.abs(ref_vals)
    obs.update(onebit_chunks=len(bounds), onebit_rtol=rtol,
               onebit_max_rel_diff=float(rel.max()))
    check(obs["onebit_max_rel_diff"] <= rtol,
          f"onebit decoded values off by {obs['onebit_max_rel_diff']:.3g} "
          f"relative (tolerance {rtol:.3g})")

    snap = bps.metrics_snapshot(light=True)
    obs["scheduler"] = snap["scheduler"]
    diag = metrics_diag()
    obs.update({k: diag[k] for k in ("aot_compiled", "aot_compile_failed",
                                     "compile_cache_miss")})
    check(obs["aot_compile_failed"] == 0,
          f"engine.aot_compile_failed = {obs['aot_compile_failed']}")
    check(obs["aot_compiled"] > 0, "declare() AOT-compiled no program")
    return obs


def _bert(ctx):
    """(cfg, model, loss_fn, host batch, host initial params) — built
    once from a seed and shared by the train phases."""
    if "bert" in ctx:
        return ctx["bert"]
    import jax

    from byteps_tpu.models.bert import (BertForMLM, bert_large, bert_tiny,
                                        mlm_loss, synthetic_batch)
    sz, n = ctx["sizes"], ctx["n"]
    cfg = bert_tiny() if ctx["rehearsal"] else bert_large()
    model = BertForMLM(cfg)
    rng = jax.random.PRNGKey(0)
    batch = synthetic_batch(rng, cfg, batch=sz["per_chip"] * n,
                            seq_len=sz["seq"])
    params = model.init(rng, batch["input_ids"][:1],
                        batch["attention_mask"][:1])

    def loss_fn(p, b):
        logits = model.apply(p, b["input_ids"], b["attention_mask"],
                             masked_positions=b["masked_positions"])
        return mlm_loss(logits, b["masked_labels"])

    ctx["bert"] = (cfg, model, loss_fn, jax.device_get(batch),
                   jax.device_get(params))
    return ctx["bert"]


def _spread(tree, n: int, what: str) -> None:
    import jax
    for leaf in jax.tree.leaves(tree):
        check(len(leaf.sharding.device_set) == n,
              f"{what}: a leaf of shape {leaf.shape} spans "
              f"{len(leaf.sharding.device_set)} of {n} devices")


def _memory_spread(devices) -> list:
    """bytes_in_use per device.  Every train phase holds the same
    replicated state plus an equal shard on each chip, so the emptiest
    chip must be within 20% of the fullest: anything more is a tree
    parked on one device (2 x params of adam state on device 0 reads as
    0.54 here)."""
    used = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    if len(devices) > 1 and all(u is not None for u in used):
        check(min(used) >= 0.8 * max(used),
              f"device memory is lopsided: bytes_in_use = {used}")
    return used


def _fused_steps(ctx, comm, steps: int, compress_dcn=None) -> dict:
    """``steps`` steps of make_dp_train_step from the shared initial
    params; compile timed apart from the steps."""
    import jax
    import numpy as np
    import optax

    from byteps_tpu.parallel import (collective_schedule,
                                     make_dp_train_step, replicate,
                                     shard_batch)
    _, _, loss_fn, batch_h, params_h = _bert(ctx)
    n = ctx["n"]
    tx = optax.adamw(1e-4)
    step = make_dp_train_step(comm, loss_fn, tx, compress_dcn=compress_dcn)
    params = replicate(comm, params_h)
    opt_state = replicate(comm, tx.init(params))
    batch = shard_batch(comm, batch_h)
    _spread(batch, n, "sharded batch")
    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, batch).compile()
    text = compiled.as_text()
    obs = {"compile_s": round(time.perf_counter() - t0, 2),
           "collective_schedule": collective_schedule(text)}
    if compress_dcn is None and n > 1 and not ctx["rehearsal"]:
        # tier-1 runs on the CPU, where no all-reduce is asynchronous:
        # this is the one place the mechanism is guarded on real chips.
        # BERT-large's two 119 MiB embeddings go asynchronous under the
        # compiler's default packing too, so "> 0" would pass with the
        # combiner threshold dead; with it, 18 FFN leaves join them (20)
        check(obs["collective_schedule"]["async"] >= MIN_ASYNC_REDUCES,
              "the DP step on several TPUs holds "
              f"{obs['collective_schedule']} collectives, under "
              f"{MIN_ASYNC_REDUCES} asynchronous (parallel.data_parallel."
              "ASYNC_REDUCE_COMPILER_OPTIONS did not engage)")
    if compress_dcn is not None and not ctx["rehearsal"]:
        check(MOSAIC_CALL in text,
              "compressed-DCN step holds no Mosaic custom call: the "
              "onebit kernels were replaced by the jnp path")
    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, batch)
        jax.block_until_ready((params, opt_state, loss))
        times.append(round(time.perf_counter() - t0, 4))
        losses.append(float(loss))
    obs["bytes_in_use"] = _memory_spread(jax.devices())
    obs.update(losses=[round(v, 5) for v in losses], step_s=times)
    check(bool(np.all(np.isfinite(losses))), f"non-finite loss: {losses}")
    ctx.setdefault("raw_losses", {})[
        "dcn" if compress_dcn is not None else "fused"] = losses
    return obs


def phase_fused_train(ctx) -> dict:
    from byteps_tpu.comm.mesh import get_comm
    cfg = _bert(ctx)[0]
    obs = {"model": {"layers": cfg.num_layers, "hidden": cfg.hidden_size,
                     "heads": cfg.num_heads, "ffn": cfg.intermediate_size,
                     "vocab": cfg.vocab_size},
           "seq": ctx["sizes"]["seq"], "per_chip": ctx["sizes"]["per_chip"]}
    obs.update(_fused_steps(ctx, get_comm(), steps=5))
    losses = ctx["raw_losses"]["fused"]
    check(losses[-1] < losses[0],
          f"loss did not fall over 5 steps: {losses}")
    return obs


def phase_engine_train(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    import byteps_tpu as bps
    from byteps_tpu.comm.mesh import get_comm
    from byteps_tpu.jax import DistributedOptimizer
    from byteps_tpu.parallel import replicate
    from tools._bench_util import metrics_diag

    _, _, loss_fn, batch_h, params_h = _bert(ctx)
    comm, n, per = get_comm(), ctx["n"], ctx["sizes"]["per_chip"]
    axes = comm.dp_axes

    def stacked(ndim):
        return comm.stacked_sharding(extra_dims=ndim)

    # rank r's examples, gradients and loss live on chip r
    batch = {k: jax.device_put(v.reshape((n, per) + v.shape[1:]),
                               stacked(v.ndim))
             for k, v in batch_h.items()}
    _spread(batch, n, "rank-stacked batch")
    params = replicate(comm, params_h)

    def per_rank(p, b):
        loss, g = jax.value_and_grad(loss_fn)(
            p, jax.tree.map(lambda v: v[0], b))
        return loss[None], jax.tree.map(lambda v: v[None], g)

    grad_fn = jax.jit(
        jax.shard_map(per_rank, mesh=comm.mesh, in_specs=(P(), P(axes)),
                      out_specs=(P(axes), P(axes)), check_vma=False),
        out_shardings=(stacked(0),
                       jax.tree.map(lambda v: stacked(v.ndim), params)))
    t0 = time.perf_counter()
    grad_c = grad_fn.lower(params, batch).compile()
    obs = {"grad_compile_s": round(time.perf_counter() - t0, 2)}

    tx = optax.adamw(1e-4)
    # The optax update and the apply are the user's own code, jitted the
    # way a training script would; the push_pull between them is
    # host-driven.  init stays eager: its zeros do not depend on the
    # params' VALUES, so a jitted init drops its mesh-placed inputs and
    # parks the whole optimizer state on device 0 (seen on four chips).
    opt = DistributedOptimizer(optax.GradientTransformation(
        tx.init, jax.jit(tx.update)))
    state = opt.init(params)
    apply = jax.jit(optax.apply_updates, donate_argnums=(0,),
                    out_shardings=comm.replicated_sharding())
    before = metrics_diag()
    losses, times = [], []
    for i in range(3):
        t0 = time.perf_counter()
        rank_loss, grads = grad_c(params, batch)
        if i == 0:
            _spread(grads, n, "rank-stacked gradients")
            obs["bytes_in_use"] = _memory_spread(jax.devices())
        updates, state = opt.update(grads, state, params)
        del grads
        params = apply(params, updates)
        jax.block_until_ready(params)
        times.append(round(time.perf_counter() - t0, 3))
        losses.append(float(jnp.mean(rank_loss)))
    after = metrics_diag()
    obs.update(losses=[round(v, 5) for v in losses], step_s=times,
               leaves=len(jax.tree.leaves(params)),
               engine_programs_compiled=after["compile_cache_miss"]
               - before["compile_cache_miss"],
               aot_compile_failed=after["aot_compile_failed"],
               scheduler=bps.metrics_snapshot(light=True)["scheduler"])
    check(bool(np.all(np.isfinite(losses))), f"non-finite loss: {losses}")
    fused = ctx.get("raw_losses", {}).get("fused")
    check(fused is not None, "no fused-phase losses to compare against")
    obs["fused_losses"] = [round(v, 5) for v in fused[:3]]
    # bf16 compute: 8 mantissa bits, and the two paths sum in different
    # orders — agreement to ~1% of the loss is what the dtype promises
    np.testing.assert_allclose(losses, fused[:3], rtol=1e-2)
    check(after["aot_compile_failed"] == 0,
          f"engine.aot_compile_failed = {after['aot_compile_failed']}")
    return obs


def _compile(ctx, what: str, fn, *args):
    """AOT-compile ``fn`` for ``args`` and return the executable; on the
    chip, first assert from the compiled text that it holds a Mosaic
    custom call — "ran" is not enough, the kernel must not have been
    interpreted or replaced by the jnp path."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    if not ctx["rehearsal"]:
        check(MOSAIC_CALL in compiled.as_text(),
              f"{what} holds no Mosaic custom call")
    return compiled


def phase_kernels(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.compression.onebit import OnebitCompressor
    from byteps_tpu.ops import flash_attention
    from byteps_tpu.parallel import full_attention
    from tests import compression_refs as refs

    obs = {"flash": [], "mosaic_custom_call": not ctx["rehearsal"]}

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def exact(q, k, v):
        return full_attention(q, k, v, causal=True)

    def grads(f):
        return jax.grad(
            lambda q, k, v, w: jnp.sum(f(q, k, v).astype(jnp.float32) * w),
            argnums=(0, 1, 2))

    def maxdiff(a, b_):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b_.astype(jnp.float32))))

    for (b, t, h, d) in ctx["sizes"]["flash"]:
        ks = jax.random.split(jax.random.PRNGKey(t + d), 4)
        q, k, v = (jax.random.normal(kk, (b, t, h, d), jnp.bfloat16)
                   for kk in ks[:3])
        # A fixed random cotangent (not all-ones), scaled so the bf16
        # gradients stay below 4: there one bf16 ulp is <= 2^-6, so two
        # correct roundings can meet the absolute 2e-2 bound at all.
        w = jax.random.normal(ks[3], (b, t, h, d), jnp.float32) / 8
        t0 = time.perf_counter()
        fwd = _compile(ctx, "flash forward", flash, q, k, v)
        bwd = _compile(ctx, "flash backward", grads(flash), q, k, v, w)
        compile_s = time.perf_counter() - t0
        got, gq = fwd(q, k, v), bwd(q, k, v, w)
        want = jax.jit(exact)(q, k, v)
        wq = jax.jit(grads(exact))(q, k, v, w)
        row = {"shape": [b, t, h, d], "fwd_maxdiff": maxdiff(got, want),
               "grad_maxdiff": max(maxdiff(a, b_) for a, b_ in zip(gq, wq)),
               "grad_absmax": max(float(jnp.max(jnp.abs(g))) for g in wq),
               "compile_s": round(compile_s, 3)}
        obs["flash"].append(row)
        for key in ("fwd_maxdiff", "grad_maxdiff"):
            check(np.isfinite(row[key]) and row[key] <= 2e-2,
                  f"flash {key} = {row[key]} at {row['shape']}")

    # onebit through the codec the engine and the DCN hop both call
    numel = 32 * 128 * (8 if ctx["rehearsal"] else 1024)
    comp = OnebitCompressor(numel)
    x = np.random.RandomState(3).standard_normal(numel).astype(np.float32)

    def pack(v):
        return comp.compress(v, comp.init_state())[0]

    def merge(words, scales):
        return comp.decompress_sum({"words": words, "scale": scales})

    xd = jnp.asarray(x)
    payload = _compile(ctx, "onebit pack", pack, xd)(xd)
    ref_words, ref_scale = refs.onebit_compress(x)
    check(np.array_equal(np.asarray(payload["words"]), ref_words),
          "onebit packed words differ from the numpy reference")
    rtol = sum_order_rtol(numel, reductions=1)
    np.testing.assert_allclose(float(payload["scale"]), ref_scale,
                               rtol=rtol)
    dec = np.asarray(_compile(ctx, "onebit unpack", comp.decompress,
                              payload)(payload))
    np.testing.assert_allclose(
        dec, refs.onebit_decompress(ref_words, ref_scale, numel), rtol=rtol)
    obs["onebit_scale_rel_diff"] = abs(
        float(payload["scale"]) - float(ref_scale)) / float(ref_scale)
    for R in (2, 4):       # the gathered merge at the DCN / 4-rank widths
        words = jnp.stack([payload["words"]] * R)
        scales = jnp.arange(1, R + 1, dtype=jnp.float32)
        got = np.asarray(_compile(ctx, f"onebit merge (R={R})", merge,
                                  words, scales)(words, scales))
        want = refs.onebit_decompress(ref_words, np.float32(1), numel) \
            * np.float32(R * (R + 1) / 2)
        np.testing.assert_allclose(got, want, rtol=1e-6)
    obs["onebit_numel"] = numel
    return obs


def phase_dcn(ctx) -> dict:
    import byteps_tpu as bps
    from byteps_tpu.comm.mesh import get_comm
    from byteps_tpu.common.config import Config
    from byteps_tpu.ops import make_onebit_pair

    n = ctx["n"]
    bps.shutdown()
    bps.init(Config(dcn_size=2))
    comm = get_comm()
    check((comm.n_dcn, comm.n_ici) == (2, n // 2),
          f"mesh is ({comm.n_dcn}, {comm.n_ici}); expected (2, {n // 2})")
    obs = {"mesh": [comm.n_dcn, comm.n_ici]}
    obs.update(_fused_steps(ctx, comm, steps=2,
                            compress_dcn=make_onebit_pair()))
    return obs


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny CPU run of the control flow; never a result")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma list (debugging); the contract is all of "
                         "them, which is the default")
    args = ap.parse_args(argv)
    want = [p for p in args.phases.split(",") if p]
    unknown = sorted(set(want) - set(PHASES))
    if unknown:
        ap.error(f"unknown phase(s) {unknown}; choose from {PHASES}")

    if args.rehearsal:
        # the rehearsal is the ONLY way onto the CPU, and it says so
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()
        # tiny DCN shards must still take the compressed hop
        os.environ["BYTEPS_DCN_COMPRESS_MIN_BYTES"] = "0"

    import jax

    from byteps_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    if not args.rehearsal and devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU — JAX reports platform "
              f"{devices[0].platform!r} ({len(devices)} device(s)). "
              f"There is no CPU fallback; `--rehearsal` runs a labelled "
              f"toy of the control flow.", file=sys.stderr)
        return 2

    ctx = {"rehearsal": args.rehearsal, "n": len(devices),
           "sizes": sizes(args.rehearsal), "cache_dir": cache_dir}
    fns = {"device": phase_device, "engine": phase_engine,
           "fused_train": phase_fused_train,
           "engine_train": phase_engine_train, "kernels": phase_kernels,
           "dcn": phase_dcn}
    # persistent-cache traffic per phase: on a warm cache the same
    # programs hit instead of compiling
    xla_cache = {"hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            xla_cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            xla_cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    dog = Watchdog()
    # "dcn" needs a (2, n/2) mesh: below four devices it is not a phase
    # this machine can run, which is different from skipping one
    runnable = [p for p in PHASES
                if p != "dcn" or (ctx["n"] >= 4 and ctx["n"] % 2 == 0)]
    ran = [p for p in runnable if p in want]
    failed = []
    for name in ran:
        line = {"phase": name}
        if args.rehearsal:
            line.update(rehearsal=True, device="cpu")
        dog.arm(name, PHASE_BUDGET_S[name] * (2 if ctx["n"] > 1 else 1))
        cache0 = dict(xla_cache)
        t0 = time.perf_counter()
        try:
            line.update(fns[name](ctx))
            line["pass"] = True
        except Exception as e:  # noqa: BLE001 — recorded as a FAILED phase
            traceback.print_exc(file=sys.stderr)
            line.update({"pass": False,
                         "error": f"{type(e).__name__}: {e}"[:800]})
            failed.append(name)
        finally:
            dog.disarm()
        line["phase_s"] = round(time.perf_counter() - t0, 2)
        line["xla_cache"] = {k: xla_cache[k] - cache0[k] for k in xla_cache}
        line["peak_bytes_in_use"] = peak_bytes(devices)
        emit(line)
        if name == "device" and failed:
            break
    try:
        import byteps_tpu as bps
        bps.shutdown()
    except Exception:  # noqa: BLE001 — teardown must not mask the verdict
        traceback.print_exc(file=sys.stderr)

    final = {"ok": not failed}
    if args.rehearsal:
        final.update(rehearsal=True, device="cpu", count=len(devices))
    else:
        final["device"] = {"platform": devices[0].platform,
                           "kind": devices[0].device_kind,
                           "count": len(devices)}
    if failed:
        final["failed"] = failed
    if ran != runnable:
        final["partial"] = ran   # a --phases subset is not the contract
    emit(final)
    if failed:
        print(f"chip_smoke: FAILED phase(s): {', '.join(failed)}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

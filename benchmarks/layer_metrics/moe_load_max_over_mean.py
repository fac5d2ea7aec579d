"""Load imbalance of the expert layer: gauge ``moe.load_max_over_mean``
(the fullest expert's token-expert pairs over the mean, worst layer;
1.0 = balanced), as ``byteps_tpu.parallel.expert.publish_moe_stats`` sets
it in the registry ``bps.metrics_snapshot()`` reads.  Published here,
after the window, from ONE batch: rank 0's shard of the run's batch 0
under the seeded initial parameters (``run.py`` has no hook between
warm-up and window, and the runner's state is freed by now).  The
slowest expert group bounds the grouped matmuls, and a later
expert-parallel cell's all_to_all."""

UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "parallel.expert (dropless MoE)"
MOVES = "tokens_per_s_per_chip"

NAME = "moe.load_max_over_mean"


def read(run):
    counts_of = getattr(run.family, "expert_counts", None)
    if counts_of is None:
        return None
    try:
        from byteps_tpu.parallel.expert import publish_moe_stats
    except ImportError:          # a program without the dropless layer
        return None
    import byteps_tpu as bps
    import jax
    job, fam = run.job, run.family
    params = jax.jit(fam.init_params)(job.param_key)
    batch = jax.jit(fam.make_batch, static_argnums=1)(job.batch_key(0),
                                                      job.global_seqs)
    shard = jax.tree.map(lambda a: a[:job.seqs_per_chip], batch)
    publish_moe_stats(jax.jit(counts_of)(params, shard))
    return bps.metrics_snapshot()["gauges"].get(NAME)

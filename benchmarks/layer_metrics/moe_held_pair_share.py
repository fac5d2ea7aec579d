"""Share of a step's token-expert pairs that fall on the experts this chip
holds: gauge ``moe.held_pair_share``, as ``byteps_tpu.parallel.expert.
publish_moe_stats(counts, held=...)`` sets it (with
``moe.held_load_max_over_mean``) in the registry ``bps.metrics_snapshot()``
reads.  It is the live share of each layer's ``tokens x experts per
token`` pair rows — the rest ride through the row gathers dead — and
``held / routed`` (0.25) under a balanced router.  Published here, after
the window, from ONE batch, as ``moe_load_max_over_mean`` is: rank 0's
shard of the run's batch 0 under the seeded initial parameters."""

UNIT = "ratio"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "parallel.expert (dropless MoE)"
MOVES = "tokens_per_s_per_chip"

NAME = "moe.held_pair_share"


def read(run):
    if NAME in run.info:                 # held_moe_roofline asked already
        return run.info[NAME]
    held = getattr(run.family, "experts_held", None)
    if held is None:
        return None
    import jax
    import byteps_tpu as bps
    from byteps_tpu.parallel.expert import publish_moe_stats
    job, fam = run.job, run.family
    params = jax.jit(fam.init_params)(job.param_key)
    batch = jax.jit(fam.make_batch, static_argnums=1)(job.batch_key(0),
                                                      job.global_seqs)
    shard = jax.tree.map(lambda a: a[:job.seqs_per_chip], batch)
    counts = jax.jit(fam.expert_counts)(params, shard)
    try:
        publish_moe_stats(counts, held=held)
    except TypeError:          # a program whose layer knows no share
        return None
    gauges = bps.metrics_snapshot()["gauges"]
    run.info["moe.held_load_max_over_mean"] = gauges.get(
        "moe.held_load_max_over_mean")
    run.info[NAME] = gauges.get(NAME)
    return run.info[NAME]

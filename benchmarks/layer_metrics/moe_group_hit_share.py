"""Share of a step's tokens whose chosen routing groups include the group
of the experts this chip holds: gauge ``moe.group_hit_share``, as
``byteps_tpu.models.ling.publish_group_stats`` sets it from the model's
own ``group_hit_share`` (the mean over the sparse layers) of ONE seeded
batch after the window — rank 0's shard of the run's batch 0 under the
seeded initial parameters, as ``moe_held_pair_share`` publishes its own.
Under group-limited routing a token reaches a held expert only through a
chosen group: ``topk_group / n_group`` (0.5) under a balanced router.
``moe.held_pair_share`` (0.0156 balanced), ``moe.window_trips`` and
``moe.visited_row_share`` of the same batch go on the ``info`` line.  A
family without such routing gives nothing."""

from harness import spec

UNIT = "ratio"
BETTER = "higher"
SOURCE = "program_counter"
LAYER = "parallel.expert (dropless MoE)"
MOVES = "tokens_per_s_per_chip"

NAME = "moe.group_hit_share"


def read(run):
    job, fam = run.job, run.family
    if not hasattr(fam, "group_hit_share"):
        return None
    import jax
    import byteps_tpu as bps
    params = jax.jit(fam.init_params)(job.param_key)
    batch = jax.jit(fam.make_batch, static_argnums=1)(job.batch_key(0),
                                                      job.global_seqs)
    shard = jax.tree.map(lambda a: a[:job.seqs_per_chip], batch)
    fam.publish_group_stats(jax.jit(fam.group_hit_share)(params, shard))
    # the pair counts of the same batch: moe_held_pair_share publishes them
    spec.load_module("layer_metrics", "moe_held_pair_share").read(run)
    gauges = bps.metrics_snapshot()["gauges"]
    for name in ("moe.window_trips", "moe.visited_row_share", "moe.groups",
                 "moe.groups_chosen"):
        run.info[name] = gauges.get(name)
    return gauges.get(NAME)

"""Share of their roofline the LatentMoE's routed experts' grouped matmuls
reach: the least time the chip could take for the operations and HBM bytes
of the pair rows that fall on held experts, at the EXPECTED share (``held /
routed`` = 8 / 512 of the ``22 N`` pairs: ``families/nemotron_h.py``
``moe_work``; two matmuls, three passes, the held matrices touched once a
pass; the forward recomputed under ``remat`` is not the algorithm's work)
over ``latent_moe_ms``.  Which roof binds, the share the published batch
really had and the roofline rescaled by it go to the ``info`` line."""

from harness import kernel_time, spec

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    work = run.kernel_work.get("latent_moe")
    seconds = kernel_time.seconds(run, "latent_moe")
    if not work or not seconds:
        return None
    value, run.info["latent_moe_roofline_bound"] = (
        kernel_time.roofline_share(work, seconds, run.peaks))
    had = spec.load_module("layer_metrics", "latent_held_pair_share").read(run)
    if had:
        real = run.family.latent_moe_work(run.job.seqs_per_chip,
                                          pair_share=had)
        run.info["latent_moe_roofline_pct_at_real_share"] = (
            kernel_time.roofline_share(real, seconds, run.peaks)[0])
    return value

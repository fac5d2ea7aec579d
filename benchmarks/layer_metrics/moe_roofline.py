"""Share of their roofline the expert layer's grouped matmuls reach: the
least time the chip could take for the operations and HBM bytes the
ALGORITHM needs at this cell's shape (``families/olmoe.py`` ``moe_work``:
the ACTIVE experts' three matmuls in three passes) over the measured
kernel time.  Which roof binds goes to the ``info`` line — and with it the
flash kernels' share in this cell (head size 128), which ``flash_roofline``
does not list (PERF.md section 7)."""

from harness import flops, spec

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def _share(work, seconds, peaks):
    roof = flops.roofline(work["flops"], work["bytes"], peaks)
    return 100.0 * roof["seconds"] / seconds, roof["bound"]


def read(run):
    work = run.kernel_work.get("moe")
    seconds = spec.load_module("layer_metrics", "moe_ms").moe_seconds(run)
    if not work or not seconds:
        return None
    share, run.info["moe_roofline_bound"] = _share(work, seconds, run.peaks)
    flash = run.kernel_work.get("flash")
    flash_s = spec.load_module("layer_metrics", "flash_ms").flash_seconds(run)
    if flash and flash_s:
        (run.info["flash_roofline_pct"],
         run.info["flash_roofline_bound"]) = _share(flash, flash_s, run.peaks)
    return share

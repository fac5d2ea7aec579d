"""Share of their roofline the sliding-window layers' flash kernels reach:
the least time the chip could take for the operations and HBM bytes the
BAND needs (``families/mellum.py`` ``flash_work``: row i sees ``min(i + 1,
window)`` keys; k/v counted at the key/value heads) over ``swa_flash_ms``.
Which roof binds, and the full layers' share (all flash kernels less the
sliding layers', against the causal count), go to the ``info`` line."""

from harness import kernel_time

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    work = run.kernel_work.get("swa_flash")
    seconds = kernel_time.seconds(run, "swa_flash")
    if not work or not seconds:
        return None
    value, run.info["swa_flash_roofline_bound"] = (
        kernel_time.roofline_share(work, seconds, run.peaks))
    full = run.kernel_work.get("full_flash")
    all_s = kernel_time.seconds(run, "flash")
    if full and all_s and all_s > seconds:
        run.info["full_flash_ms"] = (all_s - seconds) * 1e3
        (run.info["full_flash_roofline_pct"],
         run.info["full_flash_roofline_bound"]) = (
             kernel_time.roofline_share(full, all_s - seconds, run.peaks))
    return value

"""A family's Mosaic kernels in a trace: their device time, and that time
against the roofline of the work the family says they need.

``run.kernel_work[<name>]`` is ``{"flops", "bytes", "op_name_re"}``; the
kernels are the program's ``tpu_custom_call`` instructions whose HLO
``op_name`` the rule matches (``run.mosaic``, from ``xplane.mosaic_ops``).
"""

from __future__ import annotations

import re

from . import flops, xplane


def seconds(run, work: str):
    """Seconds a step in the kernels ``run.kernel_work[work]`` names; None
    without a trace or without such work."""
    rule = run.kernel_work.get(work, {}).get("op_name_re")
    if run.trace is None or rule is None:
        return None
    names = [i for i, op in run.mosaic.items() if re.search(rule, op)]
    return xplane.op_seconds(run.trace, names, run.window.traced_steps)


def roofline_share(work: dict, kernel_seconds: float, peaks: dict):
    """(percent of its roofline, which roof binds) of kernels that took
    ``kernel_seconds`` for ``work``'s operations and bytes."""
    roof = flops.roofline(work["flops"], work["bytes"], peaks)
    return 100.0 * roof["seconds"] / kernel_seconds, roof["bound"]

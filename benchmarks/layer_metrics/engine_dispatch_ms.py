"""Median over the window's steps of ``attrib.plan + attrib.dispatch +
attrib.compile``: the dispatcher thread WORKING — carving popped tasks into
units (span ``bps.engine.plan``) and launching one program per unit
(``bps.engine.dispatch``; ``compile`` is a launch that crossed a
compile-cache miss and is absent from a step without one).  0 where the
engine saw no step."""

from harness.step_stats import window_median

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "core.engine + common.scheduler"
MOVES = "tokens_per_s_per_chip"


def read(run):
    def dispatcher_ms(step):
        a = step["attrib"]
        return a["plan"] + a["dispatch"] + a.get("compile", 0.0)
    return window_median(run, dispatcher_ms)

"""Windows a held-experts layer call ran over its live range, worst layer:
gauge ``moe.window_trips``, as ``byteps_tpu.parallel.expert.
publish_moe_stats(counts, held=...)`` sets it beside
``moe.visited_row_share`` where the layer's shapes make it work in windows
(``window_rows``: a thin held share) — ``window_trips(counts, held, W)``,
the arithmetic the layer's loops take their trip count from: 1.0 where the
rows routed here fit one window of twice their expectation, more where the
router sent more (nothing is dropped; the layer runs longer).  Published
by ``moe_held_pair_share``'s reader (after the window, ONE batch); the
share of the pair rows those windows hold, ``moe.visited_row_share``, goes
on the ``info`` line.  A program whose layer works on whole arrays sets no
such gauge, and this returns nothing."""

from harness import spec

UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "parallel.expert (dropless MoE)"
MOVES = "tokens_per_s_per_chip"

NAME = "moe.window_trips"


def read(run):
    if spec.load_module("layer_metrics", "moe_held_pair_share").read(
            run) is None:
        return None
    import byteps_tpu as bps
    gauges = bps.metrics_snapshot()["gauges"]
    if NAME in gauges:
        run.info["moe.visited_row_share"] = gauges.get(
            "moe.visited_row_share")
    return gauges.get(NAME)

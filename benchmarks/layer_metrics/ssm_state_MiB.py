"""MiB of float32 state one state-space scan holds: gauge
``ssm.state_bytes`` (the state carried along one sequence: heads x state x
head size x 4) + ``ssm.saved_state_bytes`` (the chunk-start states one
differentiated call stores for its backward kernel: sequences x chunks of
them; 0 for a backward that recomputes), set beside ``ssm.heads_held``,
``ssm.chunk`` and ``ssm.chunks_per_seq`` (on the ``info`` line) while
``byteps_tpu/ops/ssd_scan.py`` ``ssd_scan`` is traced, as
``bps.metrics_snapshot()`` reads them after the window.  What a later PR
that recomputes, shrinks or re-chunks the saved states is read against."""

UNIT = "MiB"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    gauges = run.snap1.get("gauges", {})
    state = gauges.get("ssm.state_bytes")
    if state is None:
        return None
    for name in ("ssm.heads_held", "ssm.chunk", "ssm.chunks_per_seq"):
        run.info[name] = gauges.get(name)
    return (state + gauges.get("ssm.saved_state_bytes", 0.0)) / 2 ** 20

"""MiB of float32 state one delta-rule scan holds: gauge
``kda.state_bytes`` (the state carried along one sequence: heads x d_k x
d_v x 4) + ``kda.saved_state_bytes`` (the chunk-start states one
differentiated call stores for its backward kernel: sequences x chunks of
them; 0 for a backward that recomputes), set beside ``kda.heads``,
``kda.chunk``, ``kda.chunks_per_seq`` and ``kda.log_decay_floor`` (on the
``info`` line) while ``byteps_tpu/ops/kda_scan.py`` ``kda_scan`` is
traced, as ``bps.metrics_snapshot()`` reads them after the window.  What
a later PR that recomputes, shrinks or re-chunks the saved states is read
against."""

UNIT = "MiB"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    gauges = run.snap1.get("gauges", {})
    state = gauges.get("kda.state_bytes")
    if state is None:
        return None
    for name in ("kda.heads", "kda.chunk", "kda.chunks_per_seq",
                 "kda.log_decay_floor"):
        run.info[name] = gauges.get(name)
    return (state + gauges.get("kda.saved_state_bytes", 0.0)) / 2 ** 20

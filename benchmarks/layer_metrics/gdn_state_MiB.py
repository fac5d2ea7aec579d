"""MiB of float32 state one head-decay delta-rule scan holds: gauge
``gdn.state_bytes`` (the state carried along one sequence: value heads x
d_k x d_v x 4) + ``gdn.saved_state_bytes`` (the chunk-start states one
differentiated call stores for its backward kernel: sequences x chunks of
them), set beside ``gdn.heads``, ``gdn.key_heads``, ``gdn.chunk``,
``gdn.chunks_per_seq`` and ``gdn.matmul_operand_bytes_per_chunk`` (on the
``info`` line) while ``byteps_tpu/ops/gdn_scan.py`` ``gdn_scan`` is
traced, as ``bps.metrics_snapshot()`` reads them after the window.  What a
later PR that recomputes, shrinks or re-chunks the saved states is read
against.  A program without the gauge gives nothing."""

UNIT = "MiB"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    gauges = run.snap1.get("gauges", {})
    state = gauges.get("gdn.state_bytes")
    if state is None:
        return None
    for name in ("gdn.heads", "gdn.key_heads", "gdn.chunk",
                 "gdn.chunks_per_seq", "gdn.matmul_operand_bytes_per_chunk"):
        run.info[name] = gauges.get(name)
    return (state + gauges.get("gdn.saved_state_bytes", 0.0)) / 2 ** 20

"""KiB the delta-rule scan's chunk hands to the matrix unit: gauge
``kda.matmul_operand_bytes_per_chunk`` ÷ 1024, set beside ``kda.heads``
and ``kda.chunk`` while ``byteps_tpu/ops/kda_scan.py`` ``kda_scan`` is
traced — the bytes of both operands of every ``dot_general`` in the jaxpr
of ONE head's ``_chunk_forward`` at the call's shapes and types (the
program walks its own text: the forward kernel, the forward recomputed
under ``remat`` and, through ``jax.vjp``, the backward kernel run it).  At
C = 128, heads of 128 x 128 and bfloat16 q, k, v: 6 336 for sixty [128,
128] x [128, 128] products of which 39 on float32 pieces and 16 score
products over all 128 rows; 5 632 with the score products of a row
sub-block's own 32 stacked rows (PR 45; 3 136 were the float32 pieces
handed over as bfloat16).  What ``kda_scan_ms`` is read against when the
products change.  A program without the gauge gives nothing."""

UNIT = "KiB"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    value = run.snap1.get("gauges", {}).get(
        "kda.matmul_operand_bytes_per_chunk")
    return None if value is None else value / 1024

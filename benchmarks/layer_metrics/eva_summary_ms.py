"""Device milliseconds per step in the flash kernels that attend the chunk
SUMMARIES of EVA attention: the Mosaic calls under ``bps.eva.summary``
(``byteps_tpu/ops/eva_attention.py``: the flash forward and backward under
the staircase mask ``stair=(window, window / chunk)``, every layer's — the
forward, the forward recomputed under ``remat`` and the backward, which
past the resident form is two kernels).  The pooling that makes the
summaries and the merge of the two key sets are plain XLA and not in it.
The flash calls a traced step made under the model's ``attn`` scope, BOTH
key sets, go on the ``info`` line (``eva_calls_per_step``) beside the
summary set's own (``eva_summary_calls_per_step``) — whether the mechanism
engaged.  A program without such kernels gives nothing.

DEPENDS ON ANOTHER FAMILY'S METRIC FILE: the counting (kernels under a
scope by a rule: their ms a step, their calls on the ``info`` line) is
``layer_metrics/gdn_rows_ms.py``'s ``kernels_ms``, as ``gdn_scan_ms.py``
takes it — an edit to that function changes what ``eva_summary_ms``,
``eva_calls_per_step`` and ``eva_summary_roofline`` read.  Its place is
``harness/kernel_time.py`` beside ``seconds``, which only a ``benchmark``
PR may edit (``PERF.md`` section 7 asks for the move)."""

import re

from harness import spec

UNIT = "ms/step"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "ops kernels"
MOVES = "tokens_per_s_per_chip"


def read(run):
    rule = run.kernel_work.get("eva_summary", {}).get("op_name_re")
    if rule is None:
        return None
    kernels_ms = spec.load_module("layer_metrics", "gdn_rows_ms").kernels_ms
    both = run.kernel_work.get("flash", {}).get("op_name_re")
    if both is not None:
        kernels_ms(run, re.compile(both), "eva_calls_per_step")
    return kernels_ms(run, re.compile(rule), "eva_summary_calls_per_step")

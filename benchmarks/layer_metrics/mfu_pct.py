"""Model FLOP/s utilisation: required matmul operations per token (6 per
weight a token meets, embedding gathers excluded, plus the attention
term, causal halved, recomputation not counted) x tokens per second per
chip / the chip's published bf16 peak.  Tokens per second from the mean
step time of the blocking run."""

UNIT = "%"
BETTER = "higher"
SOURCE = "host_clock"
LAYER = "device"
MOVES = "tokens_per_s_per_chip"


def read(run):
    s = run.window.step_s or run.window.traced_step_s
    if not s:
        return None
    tokens_per_s = run.job.tokens_per_step_per_chip * len(s) / sum(s)
    return (100.0 * tokens_per_s * run.family.flops_per_token
            / run.peaks["bf16_flops_per_s"])

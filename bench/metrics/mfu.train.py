"""Model FLOP utilisation of the training steps: the operations the
window's steps need, forward and backward over every token of each
step's global batch once (``counts.dense_flops_per_token``; the
recomputed activations and the replicated work of check steps are not
counted, so the protocol's cost lowers it), over the seconds of the
program's ``train.step`` spans times the workers' chips times each
chip's bf16 peak."""
from bench import counts


def read(ctx):
    steps = [s for s in ctx.program_spans if s["name"] == "train.step"]
    if not steps:
        return None
    seq = ctx.traffic["seq_len"]
    flops = (len(steps) * ctx.traffic["global_batch"] * seq
             * counts.dense_flops_per_token(ctx.config["model"], seq))
    seconds = sum(s["dur_ns"] for s in steps) / 1e9
    chips = ctx.config["protocol"]["n"]
    return 100.0 * flops / (seconds * chips * ctx.peaks["bf16_flops_per_s"])

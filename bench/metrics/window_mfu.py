"""Model FLOP utilization of the whole window, in percent: the model
FLOPs of the training steps and evaluation passes whose batches were
served inside the window, over window seconds x chips x the chip's bf16
peak. A batch drawn inside a ``client.train`` span is a training step,
any other an evaluation pass."""
from fedbench import flops


def read(ctx):
    if ctx.peaks is None or not (ctx.train_batches or ctx.eval_batches):
        return None
    m = ctx.config["model"]
    b, s = ctx.config["federation"]["batch_size"], ctx.config["federation"]["seq_len"]
    work = (ctx.train_batches * flops.train_step_flops(m, b, s)
            + ctx.eval_batches * flops.forward_flops(m, b, s))
    return 100.0 * work / (ctx.window_s * ctx.chips
                           * ctx.peaks["bf16_flops_per_s"])

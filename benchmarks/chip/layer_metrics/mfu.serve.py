"""The whole serving step's share of the chip's peak over the window: every
token the engine put through the model, a prompt's in a prefill or an output's
in a decode step (``engine.stats()``: ``prefill_tokens`` + ``decode_tokens``),
a second, times the matrix-multiply operations a forward pass needs for one
(the kind's ``forward_flops_per_token`` at the mean prompt + output length of
the window's requests), over chips times the published bf16 peak. A decode
step streams every weight for one token a row, so this is small by nature; it
is what bounds the kernels' rooflines from above when one of them leaves the
path. An unknown device kind is an error."""

from benchmarks.chip import flops


def read(record):
    c = record.clocks
    if not c.get("forward_flops_per_token"):
        return None
    return flops.mfu_percent(c["model_tokens_per_s"], c["forward_flops_per_token"],
                             c["device_kind"], c["chips"])

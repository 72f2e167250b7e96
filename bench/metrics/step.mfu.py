"""Model FLOP/s utilisation of the whole served step: the forward-pass
operations of the prompt and generated tokens of every query scored in
the window (padding not counted), over the window's length times the
chip's bf16 peak, in %."""


def read(run):
    f = run.served_flops()
    if f <= 0:
        return None
    return 100.0 * f / run.seconds / run.peaks()["bf16_flops_per_s"]

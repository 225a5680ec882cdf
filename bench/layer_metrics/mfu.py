"""The whole step's share of the chip's bf16 peak: useful model FLOPs
in the traced window over (window seconds x peak).  Useful FLOPs are
the prompt tokens computed and the tokens decoded, each with attention
over its live context; padded rows and idle slots are not counted, and
the output projection counts only for rows whose logits are used (the
architecture's counts, ``run.arch``)."""


def read(run):
    if not run.dispatches:
        return None
    a, s = run.arch, run.shapes
    total = 0.0
    for d in run.dispatches:
        for start, n, last in d.valid:
            total += (a.matmul_flops(s, n, int(last))
                      + a.attn_flops(s, start, n))
    return 100.0 * total / (run.trace.window_s * run.peaks.bf16_flops)

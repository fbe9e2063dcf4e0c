"""The whole step's share of the chip's bf16 peak, %: operations the dense
net's forward and backward need an example x examples/s/chip of the
window / peak. This step is bound by table rows, not by matmuls, so it
reads far under 1%."""


def read(ctx):
    return (100.0 * ctx["flops_per_example"] * ctx["rate"]
            / ctx["peaks"]["bf16_flops_per_s"])

"""``round_mfu`` for the silos' cell: useful model FLOPs of the rounds in
the traced window (the model's ``round_flops``: trained tokens of the
executed silos at three forward passes, every silo's test tokens at one,
the held experts counted at their expected share of top-k) over the
window's length times the chips' bf16 peak. The configuration states
float32 at ``highest``, six bfloat16 passes a matmul, which caps this near
a sixth of the peak."""

from bench.metrics.round_mfu import read  # noqa: F401

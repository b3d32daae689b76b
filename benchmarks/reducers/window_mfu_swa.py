"""The whole step's share of the chip's peak for a window-with-sinks trunk
configuration: ``window_mfu_ssm``'s code over ``harness/work_swa.py``: the
trunk's FLOPs at this chip's share on the real tokens of the traced window's
finished ticks, batch and probe (projections, the allowed pairs of window and
full layers, the dense FFN, routers and held routed experts), over window
seconds times chips times peak FLOP/s."""

import types

from benchmarks.harness import work_swa
from benchmarks.reducers import window_mfu_ssm

reduce = types.FunctionType(
    window_mfu_ssm.reduce.__code__, {**vars(window_mfu_ssm), "work_ssm": work_swa}, "reduce"
)

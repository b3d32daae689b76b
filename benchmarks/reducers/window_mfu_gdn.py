"""The whole step's share of the chip's peak for a gated-delta-rule trunk
configuration: ``window_mfu_ssm``'s code over ``harness/work_gdn.py``: the
trunk's FLOPs at this chip's share on the real tokens of the traced window's
finished ticks, batch and probe (projections, convolution, the delta rule at
its recurrent minimum, the full layer's allowed pairs, router, shared expert
and its gate, held routed experts), over window seconds times chips times
peak FLOP/s."""

import types

from benchmarks.harness import work_gdn
from benchmarks.reducers import window_mfu_ssm

reduce = types.FunctionType(
    window_mfu_ssm.reduce.__code__, {**vars(window_mfu_ssm), "work_ssm": work_gdn}, "reduce"
)

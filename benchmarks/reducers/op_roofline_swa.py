"""``op_roofline``'s reading for a window-with-sinks trunk configuration:
``op_roofline_ssm``'s code as it stands, over ``harness/work_swa.py``'s
table: the least time the chip could take for the named work of the traced
ticks over the device time of the operations whose names match the metric's
patterns, inside the traced window. Attention's work is counted sequence by
sequence (its allowed pairs, window and full layers alike), the experts'
batch by batch (their weights are read once a batch). Where no operation
matches (a program without the kernel) there is nothing to read and the
metric is left out."""

import types

from benchmarks.harness import work_swa
from benchmarks.reducers import op_roofline_ssm


def _with(function, **names):
    """``function`` of ``op_roofline_ssm`` reading ``names`` where it reads its module's own."""
    return types.FunctionType(
        function.__code__, {**vars(op_roofline_ssm), **names}, function.__name__, function.__defaults__
    )


least_time = _with(op_roofline_ssm.least_time, work_ssm=work_swa)
reduce = _with(op_roofline_ssm.reduce, least_time=least_time)

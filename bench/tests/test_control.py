"""The control: the plain reference computed in bfloat16, one precision
below the configurations' float32, put in the program's place. At a
tiny size on the CPU it must read above a committed limit of each cell,
as it does on the chip at the cells' own sizes (PERF.md). A ResNet-18
is kept in the tiny federation: its BN scales are where bfloat16 loses
the small updates that float32 keeps."""
import pytest

import calibrate
from tiny import TINY, limits, load

CELLS = ("r18x5.stage2", "zoo5.stage2")


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_above_the_limit(cell):
    cfg = dict(load("configs", cell.split(".")[0]),
               **dict(TINY, global_kind="resnet18"))
    (_, gap), = calibrate.stage2_readings(cfg, {"max_chunks": 6}, 7,
                                          ["control"])
    failed = [name for name, lim in limits(cell).items()
              if gap[name] > lim["limit"]]
    assert failed, gap

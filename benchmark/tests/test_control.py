"""The comparison with the plain reference, at test size on the CPU:
sound runs read 0 mismatched rows, and the control (the reference with
the configuration's named guarantee broken, put in the program's place)
fails. On the chip, at the cells' own sizes, ``control.py`` reads the
same numbers."""
import pytest

import run

CELLS = ["raft3.sweep", "raft5.chaos", "raft3.hunt"]


@pytest.mark.parametrize("name", CELLS)
def test_program_passes_and_control_fails(small_cell, name):
    jax, cell, cfg, traffic, devices = small_cell(name)
    m = run.measure(jax, cell, cfg, traffic, 2 ** 31 + 7, 1.0, False,
                    devices)
    prog = run.decide(cfg, traffic, m["units"])
    assert prog["correct"], prog["checks"]
    assert prog["rows"] > 0
    ctrl = run.decide(cfg, traffic, m["units"], control=cfg["control"])
    assert not ctrl["correct"]
    assert ctrl["checks"]["rows_mismatched"]["value"] > 0


def test_reference_control_name_is_checked():
    import spec

    bench = spec.load_benchmark()
    cfg = spec.config(bench, "raft3")
    ref = spec.reference(cfg)
    with pytest.raises(ValueError):
        ref.World(1, cfg["engine"], cfg["raft"], control="no_such")

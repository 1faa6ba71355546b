"""The control: the plain reference computed in float8, the step below the
configuration's bfloat16, put in the program's place, makes a run come out
not correct where the program's own reading passes. Here at a tiny size on
the CPU; on the chip at each cell's own size with ``bench/control.py``."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import control  # noqa: E402
from lib import check, spec as S  # noqa: E402
from lib.reference import Reference  # noqa: E402
from tests_support import ROOT, TINY_CONFIG, make  # noqa: E402


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    root = make(str(tmp_path_factory.mktemp("control")))
    return list(control.readings(root, "tiny-tiny-mix", 3.0, [21, 22, 23],
                                 [], 3.0, bench=S.Benchmark(root),
                                 require_tpu=False))


def test_control_fails_where_the_program_passes(readings):
    limit = TINY_CONFIG["check"]["logit_gap_limit"]
    program = [r["program_gap"] for r in readings]
    controls = [r["control_gap"] for r in readings]
    assert all(r["failed"] == 0 and r["sample"]["tokens"] > 0
               for r in readings)
    assert max(program) <= limit < min(controls)
    assert min(controls) >= 3 * max(max(program), 1e-3)
    # the control's reading is the one compared: the runs are not correct
    assert not any(r["correct"] for r in readings)
    assert all(r["checks"]["logit_gap"]["value"] == r["control_gap"]
               for r in readings)


def test_widest_gap_and_control_gap():
    ref = [np.array([[0.0, 2.0, 1.0], [3.0, 0.5, 0.0]], np.float32)]
    assert check.widest_gap(ref, [np.array([1, 0])]) == 0.0
    assert check.widest_gap(ref, [np.array([2, 1])]) == 2.5
    low = [np.array([[0.0, 1.0, 1.5], [3.0, 0.0, 0.0]], np.float32)]
    assert check.control_gap(ref, low) == 1.0


def test_reference_rows_ignore_right_padding():
    ref = Reference(S.Benchmark(ROOT).arch("dense_gqa"), dict(TINY_CONFIG),
                    5)
    seq = np.arange(1, 20, dtype=np.int32)
    a = ref.logits([seq], [np.arange(19)])[0]
    b = ref.logits([seq[:10]], [np.arange(10)])[0]
    np.testing.assert_allclose(a[:10], b, rtol=1e-5, atol=1e-5)

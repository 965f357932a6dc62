"""The control at a size a test run holds: the program with its own next
lower precision switched on (int4 weights, ``w4a8_nibble``), run through
the same harness, traffic and checks, comes out not correct where the
sound run passes; and the witness, the program's own precision
teacher-forced over the served tokens, sides with the reference."""

from bench import control, harness
from bench.tests import dry

SEED = 2**33 + 21


def test_control_fails_where_the_program_passes(tmp_path):
    root = dry.copy(tmp_path)
    dry.add_tiny(root)
    sound = dry.run(root, seed=SEED)
    ctl = dry.run(root, seed=SEED, control=True)
    assert sound["correct"] is True, sound["checks"]
    assert ctl["correct"] is False
    assert list(ctl)[-1] == "checks"
    c = ctl["checks"][dry.READING]
    assert sound["checks"][dry.READING]["value"] <= c["limit"] < c["value"]


def test_witness_reads_the_served_precision_teacher_forced(tmp_path):
    root = dry.copy(tmp_path)
    dry.add_tiny(root)
    cell = harness.load_cell(dry.CELL, root)
    limit = dry.TINY["limits"][dry.READING]
    (r,) = control.witness(cell, [SEED], 2.0, ["w8a8_nibble"], root=root,
                           require_tpu=False)
    assert r["tokens"] >= 16
    assert r["served"][dry.READING] <= limit
    assert r["w8a8_nibble"][dry.READING] <= limit

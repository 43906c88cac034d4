"""A whole run of the tiny-dense train_ckpt cell at a tiny size, with the look for a chip
skipped: sound it is correct, and each fault planted under it makes it not."""

import pytest

from chipbench import faults, tiny

CELL = "tiny-dense.train_ckpt"


def test_sound_run_is_correct(tmp_path):
    line = tiny.run(tmp_path, CELL)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) >= {"setup_s"} and list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "save_byte"])
def test_planted_fault_is_caught(tmp_path, monkeypatch, fault):
    faults.FAULTS[fault](monkeypatch)
    line = tiny.run(tmp_path, CELL)
    assert not line["correct"], line["checks"]

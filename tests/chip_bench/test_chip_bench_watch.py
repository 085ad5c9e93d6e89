"""``watch_machine.py`` writes its own silences and nothing else."""

import pytest

import chip_bench_paths as paths  # noqa: F401
import watch_machine


@pytest.mark.parametrize("gap_s,silent", [(10.0, False), (0.001, True)])
def test_the_watch_writes_each_silence_over_the_gap(tmp_path, gap_s, silent):
    log = tmp_path / "machine.log"
    watch_machine.watch(str(log), seconds=0.1, beat_s=0.02, gap_s=gap_s)
    lines = log.read_text().splitlines()
    assert lines[0].startswith("start wall ")
    assert all(line.startswith("silent ") for line in lines[1:])
    assert bool(lines[1:]) == silent
    # it appends, so one log holds every run of a call
    watch_machine.watch(str(log), seconds=0.0)
    assert log.read_text().splitlines()[len(lines)].startswith("start wall ")

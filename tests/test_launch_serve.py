"""The durable join launcher (``python -m repro.launch.serve``,
DESIGN.md §16): a run killed after N checkpoint commits and resumed from
its directory ends with the uninterrupted run's labels and spend."""
import re

import numpy as np
import pytest

from repro.launch.serve import main


@pytest.fixture(scope="module")
def uninterrupted():
    return main(["--requests", "4", "--lanes", "2"])


# after 2 commits two lanes are mid-session; after 6 two sessions are done
@pytest.mark.parametrize("kill_after", [2, 6])
def test_killed_run_resumes_to_the_uninterrupted_result(
        tmp_path, capsys, uninterrupted, kill_after):
    args = ["--requests", "4", "--lanes", "2",
            "--checkpoint-dir", str(tmp_path)]
    assert main(args + ["--kill-after", str(kill_after)]) is None
    assert "re-run with --resume" in capsys.readouterr().out
    resumed = main(args + ["--resume"])
    out = capsys.readouterr().out
    # steps count from 0; the crowd was already paid for at the kill
    committed = re.search(rf"restored step {kill_after - 1}: .* "
                          r"([\d.]+) cents already committed", out)
    assert committed and float(committed.group(1)) > 0, out
    assert "[serve] 4 join requests completed" in out
    assert sorted(resumed) == sorted(uninterrupted) == [0, 1, 2, 3]
    for rid, want in uninterrupted.items():
        got = resumed[rid]
        np.testing.assert_array_equal(got.labels, want.labels)
        np.testing.assert_array_equal(got.crowdsourced, want.crowdsourced)
        assert got.n_spent_cents == want.n_spent_cents
        assert got.round_sizes == want.round_sizes


@pytest.mark.parametrize("flag", [["--resume"], ["--kill-after", "2"]])
def test_recovery_flags_need_a_checkpoint_dir(capsys, flag):
    with pytest.raises(SystemExit) as e:
        main(flag)
    assert e.value.code == 2
    assert "require --checkpoint-dir" in capsys.readouterr().err

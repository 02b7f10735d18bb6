"""The examples are the operator-facing API surface: each runs as a script
against the live modules, at small arguments where it takes any, and
prints what its docstring promises."""
import os
import re
import runpy
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EXAMPLES = {
    # the paper's savings on the Cora-like data, and a bounded noisy loss
    "quickstart": ([], [r"transitive: .*\((9\d|100)% saved\)",
                        r"noisy crowd: +precision=\S+ recall=\S+ "
                        r"F=9\d\.\d+%"]),
    # a warm repeat over the cluster cache pays for nothing
    "query_plan": ([], [r"warm: +crowdsourced=0, .*spent=0c \(100% crowd",
                        r"same \d+ tuples"]),
    # the sharded machine phase feeds the service under every discipline
    "sharded_join": ([], [r"tau=0\.55 perfect: \d+ candidates.*"
                          r"precision=100\.00% recall=100\.00%",
                          r"blocked tau=0\.70 .*precision=100\.00%",
                          r"async gateway speedup: 1\.\d\dx"]),
    # streamed epochs label exactly and save crowd questions over resubmits
    "streaming_join": ([], [r"streaming: \d+ pairs.*precision=1\.00 "
                            r"recall=1\.00",
                            r"vs \d+ streamed \([1-9]\d*% saved\)"]),
    # the kernel's candidates through the join, the §15 serving layer and
    # the latency simulator
    "crowdsourced_join": (["--records", "128"],
                          [r"\[machine\] scored 128x128 pairs .*: \d+ "
                           r"candidates above 0\.45 \(17 of 17 true",
                           r"\[serve\] +majority pairs F=",
                           r"\[serve\] +em \+ clusters +F=.*"
                           r"cluster_tasks=[1-9]",
                           r"\[latency\] Non-Parallel"]),
}


@pytest.mark.parametrize("name", list(EXAMPLES))
def test_example_runs_and_reports(name, monkeypatch, capsys):
    """Each example runs as ``__main__`` in this process (it shares the
    worker's imports; a subprocess would pay them again)."""
    args, patterns = EXAMPLES[name]
    path = os.path.join(ROOT, "examples", f"{name}.py")
    monkeypatch.setattr(sys, "argv", [path, *args])
    runpy.run_path(path, run_name="__main__")
    out = capsys.readouterr().out
    for pattern in patterns:
        assert re.search(pattern, out), (pattern, out)

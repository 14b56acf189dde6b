"""A whole run of a cell at its rehearsal size on the CPU, in process:
everything but the look for a chip, returning the result line."""
from __future__ import annotations

import json

from bench.run import main


def result(capsys, workload: str, *flags: str) -> dict:
    assert main(["--workload", workload, "--seed", "3000000019", "--seconds", "0.5",
                 "--rehearsal", *flags]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])

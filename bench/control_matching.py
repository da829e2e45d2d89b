"""Run a matching cell with its control (``faults_matching.control``) in
the program's place and print what its comparison reads, one line per
seed: ``bench/control.py`` with the matching control.

    python3 bench/control_matching.py --workload bip.cold --seconds 1 --seeds 41 42

Every line has to read ``"correct": false``.  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import sys
from unittest import mock

import control
import faults
import faults_matching


def main(argv=None) -> int:
    with mock.patch.object(faults, "control", faults_matching.control):
        return control.main(argv)


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one benchmark cell once; see ``bench/harness.py``."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())

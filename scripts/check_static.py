"""CI wrapper for the static checks: ``python scripts/check_static.py``.

Thin shim over ``python -m repro.analysis`` that pins the repo root and
``src`` path so the job runs from any cwd.  All behavior (passes, flags,
exit-code contract) lives in ``repro.analysis.__main__``, which finishes
through ``repro.analysis.report.gate``.
"""

from __future__ import annotations

import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "src"))

from repro.analysis.__main__ import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main(["--repo-root", REPO_ROOT, *sys.argv[1:]]))

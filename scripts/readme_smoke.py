"""Smoke-execute README.md's code-block commands so the docs cannot drift.

CI runs this after the tier-1 step: every ``PYTHONPATH=src python ...`` line
inside a fenced ```bash block is executed from the repo root and must exit
0.  The pytest line is skipped (tier-1 already ran it as its own job step);
everything else -- quickstart, paper figures, serving -- runs for real, so a
README command that stops working fails the job.

    python scripts/readme_smoke.py [README.md]
"""

from __future__ import annotations

import re
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
TIMEOUT_S = 1200


def bash_blocks(text: str) -> list[str]:
    return re.findall(r"```bash\n(.*?)```", text, flags=re.DOTALL)


def runnable_commands(readme: Path) -> list[str]:
    cmds = []
    for block in bash_blocks(readme.read_text()):
        for line in block.splitlines():
            line = line.strip()
            if not line.startswith("PYTHONPATH=src python"):
                continue  # pip installs etc. are environment setup, and
                # chip_smoke.py needs a TPU: it refuses the CPU by design
            if "pytest" in line:
                continue  # tier-1 runs as its own CI step (same command);
                # re-running it here would only double the job's wall clock
            cmds.append(line)
    return cmds


def main() -> int:
    readme = Path(sys.argv[1]) if len(sys.argv) > 1 else ROOT / "README.md"
    cmds = runnable_commands(readme)
    if not cmds:
        print(f"ERROR: no runnable PYTHONPATH=src commands found in {readme}")
        return 1
    if not any("examples/quickstart.py" in c for c in cmds):
        print("ERROR: README.md no longer shows the quickstart command")
        return 1
    failures = 0
    for cmd in cmds:
        print(f"--- {cmd}")
        t0 = time.time()
        proc = subprocess.run(cmd, shell=True, cwd=ROOT, timeout=TIMEOUT_S)
        status = "ok" if proc.returncode == 0 else f"FAILED rc={proc.returncode}"
        print(f"--- {status} ({time.time() - t0:.1f}s)")
        failures += proc.returncode != 0
    if failures:
        print(f"{failures}/{len(cmds)} README command(s) failed")
        return 1
    print(f"all {len(cmds)} README command(s) ran clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Run every example as a smoke test (≅ examples/run_tests.py in the reference —
the examples double as the smoke tier of the test strategy, SURVEY.md §4)."""

import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

REPO = os.path.dirname(HERE)

env = dict(os.environ)
env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
# CPU unless the caller opts into another platform explicitly
env["JAX_PLATFORMS"] = os.environ.get("SLATE_EXAMPLES_PLATFORM", "cpu")
flags = env.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    env["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"


def main() -> int:
    failures = []
    examples = sorted(f for f in os.listdir(HERE)
                      if f.startswith("ex") and f.endswith(".py"))
    for ex in examples:
        proc = subprocess.run([sys.executable, os.path.join(HERE, ex)],
                              capture_output=True, text=True, env=env,
                              timeout=600)
        status = "ok" if proc.returncode == 0 else "FAILED"
        print(f"{ex:42s} {status}")
        if proc.returncode != 0:
            failures.append(ex)
            print(proc.stdout[-2000:])
            print(proc.stderr[-2000:])
    print(f"\n{len(examples) - len(failures)}/{len(examples)} examples pass")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

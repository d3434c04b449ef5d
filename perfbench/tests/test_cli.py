"""Without a TPU the command exits non-zero and prints no result."""

import json
import os
import subprocess
import sys

from perfbench.lib import registry


def test_exits_without_result_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "t9-grid",
         "--seed", str(2 ** 33 + 5), "--seconds", "1", "--trace", "0"],
        cwd=registry.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        try:
            assert not isinstance(json.loads(line), dict)
        except json.JSONDecodeError:
            pass
    assert "not tpu" in p.stderr

"""Run a function on several host-emulated CPU devices, in a child process.

A process fixes its device count when JAX starts, so a test that needs a
mesh of N devices runs its body in a child started with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``:

    result = multichip.call(4, "bench.tests.test_sharded:_case", arg, ...)

The child imports the module, calls the function with the JSON arguments and
prints what it returns as JSON; a failure in the child fails the test with the
end of its standard error.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def call(devices: int, target: str, *args, timeout: float = 900):
    flags = f"{os.environ.get('XLA_FLAGS', '')} --xla_force_host_platform_device_count={devices}"
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": flags.strip()}
    proc = subprocess.run([sys.executable, "-m", "bench.tests.multichip", target, json.dumps(args)],
                          cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(f"{target} on {devices} devices failed:\n{proc.stderr[-6000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main() -> None:
    import importlib

    for path in (ROOT, ROOT / "src"):
        if str(path) not in sys.path:
            sys.path.insert(0, str(path))
    module, _, function = sys.argv[1].partition(":")
    result = getattr(importlib.import_module(module), function)(*json.loads(sys.argv[2]))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

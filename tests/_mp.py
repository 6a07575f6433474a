"""Run a python snippet in a subprocess with N fake XLA host devices.

jax pins the device count at first initialization, so multi-device tests
cannot run in the pytest process (which must keep 1 device for the smoke
tests).  Each snippet runs `python -c` with XLA_FLAGS set first.
"""
import os
import subprocess
import sys

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_multidevice(code: str, n_devices: int = 8, timeout: int = 480):
    env = dict(os.environ)
    # children run on CPU host devices: a chip belongs to one process, and
    # the parent may hold it
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{n_devices}")
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(
            f"multidevice subprocess failed\n--- stdout ---\n"
            f"{proc.stdout}\n--- stderr ---\n{proc.stderr[-4000:]}")
    return proc.stdout

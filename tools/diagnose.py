#!/usr/bin/env python
"""Print environment diagnostics for bug reports (parity:
`tools/diagnose.py` — platform/python/deps/backend sections)."""
import os
import platform
import sys



def check_python():
    print("----------Python Info----------")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Build        :", platform.python_build())
    print("Arch         :", platform.architecture())


def check_os():
    print("----------System Info----------")
    print("Platform     :", platform.platform())
    print("system       :", platform.system())
    print("node         :", platform.node())
    print("release      :", platform.release())
    print("version      :", platform.version())


def check_hardware():
    print("----------Hardware Info----------")
    print("machine      :", platform.machine())
    print("processor    :", platform.processor())
    try:
        with open("/proc/cpuinfo") as f:
            n = sum(1 for line in f if line.startswith("processor"))
        print("cpu count    :", n)
    except OSError:
        pass


def check_pip_deps():
    print("----------Dependency Info----------")
    for mod in ("numpy", "jax", "jaxlib", "scipy"):
        try:
            m = __import__(mod)
            print(f"{mod:<13}: {getattr(m, '__version__', '?')}")
        except ImportError:
            print(f"{mod:<13}: not installed")


def check_mxnet_tpu(timeout=120):
    """Import the library in a CPU-pinned subprocess (diagnose itself never
    touches jax, so the backend check below can still own the chip)."""
    import subprocess

    print("----------mxnet_tpu Info----------")
    repo = os.path.abspath(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".."))
    probe = ("import time; tic = time.time(); import mxnet_tpu as mx; "
             "print('import time  : %.1fs' % (time.time() - tic)); "
             "print('version      :', getattr(mx, '__version__', 'dev')); "
             "from mxnet_tpu.ops import registry; "
             "print('ops          :', len(registry.list_ops()))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
    try:
        out = subprocess.run([sys.executable, "-c", probe],
                             capture_output=True, text=True, timeout=timeout,
                             env=env, cwd=repo)
        sys.stdout.write(out.stdout)
        if out.returncode != 0:
            tail = out.stderr.strip().splitlines()[-1] if out.stderr else "?"
            print("import FAILED:", tail)
    except subprocess.TimeoutExpired:
        print(f"import HUNG (> {timeout}s)")


def check_backend(timeout=60):
    """Backend init in a subprocess with a timeout, so diagnose always
    completes. A chip belongs to one process: run diagnose while nothing
    else holds it."""
    import subprocess

    print("----------Backend Info----------")
    print("JAX_PLATFORMS:", os.environ.get("JAX_PLATFORMS"))
    print("XLA_FLAGS    :", os.environ.get("XLA_FLAGS"))
    probe = ("import jax; print('backend      :', jax.default_backend()); "
             "print('devices      :', [str(d) for d in jax.devices()])")
    try:
        out = subprocess.run([sys.executable, "-c", probe],
                             capture_output=True, text=True, timeout=timeout)
        sys.stdout.write(out.stdout)
        if out.returncode != 0:
            tail = out.stderr.strip().splitlines()[-1] if out.stderr else "?"
            print("backend FAILED:", tail)
    except subprocess.TimeoutExpired:
        print(f"backend HUNG (> {timeout}s) — is another process holding "
              f"the chip?")


if __name__ == "__main__":
    check_python()
    check_os()
    check_hardware()
    check_pip_deps()
    check_mxnet_tpu()
    check_backend()

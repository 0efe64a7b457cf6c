#!/usr/bin/env python3
"""Environment doctor: verify everything the framework needs, report clearly.

The runnable counterpart of the reference's 372-line distro-installer
(`/root/reference/tracker/scripts/install-deps.sh`): rather than mutating the
host, it *checks* — Python deps, JAX backend and device count, the native
toolchain, the built (or buildable) C++ libraries, protoc, and optional
capture/sandbox capabilities (BPF clang target, /dev/kvm + firecracker) —
and prints one line per requirement plus a machine-readable JSON summary.

Exit code 0 iff every REQUIRED row passes.

Check-only by default (native rows verify existing build artifacts); pass
``--build`` to compile the native libraries first, or ``--fix`` to also
REMEDIATE what can be remediated — the install half of the reference's
`install-deps.sh:94-313` scope: build the native libraries, mount the BPF
filesystem, and (when apt-get exists on the host) install missing
toolchain packages.  Kernel config rows (CONFIG_BPF*) are verified like
`install-deps.sh:94-140` but can only be reported, not fixed.  Every fix
is logged and the checks re-run afterwards, so the output is always the
POST-fix state.

Usage: python scripts/check_env.py [--json] [--build] [--fix] [--skip-backend]
"""

from __future__ import annotations

import importlib
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # the doctor runs from anywhere
    sys.path.insert(0, REPO)

REQUIRED_MODULES = ["jax", "flax", "optax", "orbax.checkpoint", "numpy",
                    "grpc", "google.protobuf"]
OPTIONAL_MODULES = ["torch", "pandas", "pyarrow", "yaml", "chex", "einops"]


def check(name, fn, required=True):
    try:
        detail = fn()
        return {"name": name, "ok": True, "required": required,
                "detail": str(detail or "")}
    except Exception as e:
        return {"name": name, "ok": False, "required": required,
                "detail": f"{type(e).__name__}: {e}"}


def _module(mod):
    def fn():
        m = importlib.import_module(mod)
        return getattr(m, "__version__", "present")
    return fn


def _jax_backend():
    """Platform, device kind and count as JAX reports them, in-process."""
    import jax

    devices = jax.devices()
    return (f"{devices[0].platform} x{len(devices)} "
            f"({devices[0].device_kind})")


def _toolchain(tool):
    def fn():
        path = shutil.which(tool)
        if not path:
            raise FileNotFoundError(tool)
        return path
    return fn


_BUILD = "--build" in sys.argv

_NATIVE_LIBS = ("libnerrf_ingest.so", "libnerrf_tracestore.so",
                "libnerrf_fcdriver.so")


def _native_libs():
    """Check-only by default; --build compiles first (the rest of the repo
    also builds these on demand at first import)."""
    if _BUILD:
        out = subprocess.run(["make", "-s", "all"],
                             cwd=os.path.join(REPO, "native"),
                             capture_output=True, text=True, timeout=180)
        if out.returncode != 0:
            raise RuntimeError(out.stderr.strip()[-200:])
    build = os.path.join(REPO, "native", "build")
    missing = [l for l in _NATIVE_LIBS
               if not os.path.exists(os.path.join(build, l))]
    if missing:
        raise FileNotFoundError(
            f"{', '.join(missing)} (run `make -C native` or pass --build)")
    return ", ".join(_NATIVE_LIBS)


def _bpf_target():
    if _BUILD:
        out = subprocess.run(["make", "-s", "bpf"],
                             cwd=os.path.join(REPO, "native"),
                             capture_output=True, text=True, timeout=120)
        if out.returncode != 0:
            raise RuntimeError("clang BPF target unavailable (host capture only)")
    path = os.path.join(REPO, "native", "build", "tracepoints.o")
    if not os.path.exists(path):
        raise FileNotFoundError(
            "tracepoints.o not built (needs clang; `make -C native bpf`)")
    return "tracepoints.o"


def _kvm():
    if not os.path.exists("/dev/kvm"):
        raise FileNotFoundError("/dev/kvm (filesystem-clone sandbox will be used)")
    if shutil.which("firecracker") is None:
        raise FileNotFoundError("firecracker binary")
    return "microVM sandbox available"


def _bpffs():
    def fn():
        if not os.path.isdir("/sys/fs/bpf"):
            raise FileNotFoundError("/sys/fs/bpf missing")
        with open("/proc/mounts") as f:
            if not any(line.split()[1] == "/sys/fs/bpf" for line in f):
                raise RuntimeError("bpffs not mounted at /sys/fs/bpf")
        return "mounted"
    return fn


def _kernel_config():
    """CONFIG_BPF/BPF_SYSCALL/BPF_EVENTS, from /proc/config.gz or
    /boot/config-$(uname -r) — install-deps.sh:102-123's check."""
    def fn():
        import gzip
        import platform

        text = None
        if os.path.exists("/proc/config.gz"):
            text = gzip.open("/proc/config.gz", "rt").read()
        else:
            boot = f"/boot/config-{platform.release()}"
            if os.path.exists(boot):
                text = open(boot).read()
        if text is None:
            return "no kernel config exposed (skipping)"
        missing = [c for c in ("CONFIG_BPF=y", "CONFIG_BPF_SYSCALL=y",
                               "CONFIG_BPF_EVENTS=y")
                   if f"\n{c}" not in text and not text.startswith(c)]
        if missing:
            raise RuntimeError(f"disabled: {', '.join(missing)}")
        return "CONFIG_BPF, CONFIG_BPF_SYSCALL, CONFIG_BPF_EVENTS"
    return fn


# tool → Debian package, for the --fix apt path (install-deps.sh:128-141)
_APT_PACKAGES = {"g++": "build-essential", "make": "build-essential",
                 "clang": "clang", "protoc": "protobuf-compiler",
                 "cmake": "cmake", "ninja": "ninja-build"}


def apply_fixes(rows) -> list:
    """Remediate what a failed row allows; returns log lines.  Anything
    needing capabilities the host refuses (mount in an unprivileged
    container, no apt-get) degrades to a logged skip, never a crash."""
    fixes = []
    failed = {r["name"] for r in rows if not r["ok"]}

    # toolchain FIRST: the native build below needs the compiler a fresh
    # host may be missing — the other order can't converge in one run
    missing_tools = [t for t in _APT_PACKAGES
                     if f"toolchain:{t}" in failed]
    if missing_tools:
        if shutil.which("apt-get"):
            pkgs = sorted({_APT_PACKAGES[t] for t in missing_tools})
            r = subprocess.run(["apt-get", "install", "-y"] + pkgs,
                               capture_output=True, text=True)
            fixes.append(f"apt-get install {' '.join(pkgs)}: "
                         f"rc={r.returncode}")
        else:
            fixes.append(f"toolchain missing ({', '.join(missing_tools)}) "
                         "but no apt-get on this host — install manually")

    if "native:libraries" in failed:
        r = subprocess.run(["make", "-C", os.path.join(REPO, "native")],
                           capture_output=True, text=True)
        fixes.append(f"built native libraries: rc={r.returncode}"
                     + ("" if r.returncode == 0 else
                        f" ({r.stderr.strip().splitlines()[-1][:120]})"))

    if "kernel:bpffs" in failed and os.path.isdir("/sys/fs/bpf"):
        r = subprocess.run(["mount", "-t", "bpf", "bpf", "/sys/fs/bpf"],
                           capture_output=True, text=True)
        fixes.append(f"mount bpffs: rc={r.returncode}"
                     + ("" if r.returncode == 0 else
                        f" ({r.stderr.strip()[:120]})"))
    return fixes


def run_checks() -> list:
    rows = []
    for mod in REQUIRED_MODULES:
        rows.append(check(f"python:{mod}", _module(mod)))
    for mod in OPTIONAL_MODULES:
        rows.append(check(f"python:{mod}", _module(mod), required=False))
    if "--skip-backend" not in sys.argv:
        # the backend row initializes JAX (and so takes the chip, if there
        # is one) — CI that only validates the host image skips it
        rows.append(check("jax:backend", _jax_backend))
    for tool in ("g++", "make"):
        rows.append(check(f"toolchain:{tool}", _toolchain(tool)))
    for tool in ("clang", "protoc", "cmake", "ninja"):
        rows.append(check(f"toolchain:{tool}", _toolchain(tool), required=False))
    rows.append(check("native:libraries", _native_libs))
    rows.append(check("native:bpf-target", _bpf_target, required=False))
    rows.append(check("sandbox:kvm+firecracker", _kvm, required=False))

    def _capture_probe():
        daemon = os.path.join(REPO, "native", "build", "nerrf-trackerd")
        if not os.path.exists(daemon):
            raise FileNotFoundError("nerrf-trackerd not built (make -C native)")
        r = subprocess.run([daemon, "--probe"], capture_output=True, text=True,
                           timeout=30)
        if r.returncode == 0:
            return "live kernel capture available"
        raise PermissionError(
            {2: "no CAP_BPF (replay mode still works)",
             3: "kernel support missing (replay mode still works)"}.get(
                r.returncode, f"probe rc={r.returncode}"))

    rows.append(check("capture:live-bpf", _capture_probe, required=False))
    rows.append(check("kernel:bpffs", _bpffs(), required=False))
    rows.append(check("kernel:config", _kernel_config(), required=False))
    return rows


def main() -> int:
    rows = run_checks()
    fixes = []
    if "--fix" in sys.argv:
        fixes = apply_fixes(rows)
        if fixes:
            rows = run_checks()  # report the POST-fix state

    ok = all(r["ok"] for r in rows if r["required"])
    if "--json" in sys.argv:
        print(json.dumps({"ok": ok, "fixes": fixes or None,
                          "checks": rows}, indent=2))
    else:
        for f in fixes:
            print(f"[fix ] {f}")
        for r in rows:
            mark = "ok " if r["ok"] else ("FAIL" if r["required"] else "skip")
            print(f"[{mark}] {r['name']:28s} {r['detail']}")
        print(f"\nenvironment {'OK' if ok else 'NOT OK'}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())

"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Must run before any jax import (hence conftest, imported first by pytest).
Multi-chip sharding tests validate against this mesh; the driver separately
dry-runs `__graft_entry__.dryrun_multichip` the same way.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _refresh_native() -> None:
    """`make native` staleness gate (ISSUE 6 satellite): when
    native/fastparse.cc is newer than the prebuilt .so, rebuild BEFORE
    anything imports dmlc_core_tpu.data.native — otherwise the native
    parity suites (and every fused-kernel test) silently validate last
    round's binary. No toolchain → skip the rebuild with a visible
    reason on stderr; the source-hash stamp still flags the stale .so
    wherever it matters (the chip benchmark refuses it outright)."""
    import shutil
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(root, "native", "fastparse.cc")
    so = os.path.join(root, "native", "libdmlc_tpu_native.so")
    if not os.path.exists(src):
        return
    if os.path.exists(so) and os.path.getmtime(so) >= os.path.getmtime(src):
        return
    make = shutil.which("make")
    cxx = shutil.which(os.environ.get("CXX", "g++"))
    if not make or not cxx:
        sys.stderr.write(
            "[conftest] SKIPPING native rebuild: fastparse.cc is newer "
            "than libdmlc_tpu_native.so but no make/g++ toolchain is "
            "available — native suites run against the existing binary\n"
        )
        return
    proc = subprocess.run(
        [make, "-C", os.path.join(root, "native")],
        capture_output=True,
        text=True,
    )
    if proc.returncode != 0:
        sys.stderr.write(
            "[conftest] native rebuild FAILED; tests run against the "
            "stale binary:\n" + (proc.stdout + proc.stderr)[-2000:] + "\n"
        )


_refresh_native()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: spawns subprocesses with fresh jax imports"
    )
    config.addinivalue_line(
        "markers",
        "jax: imports jax in-process (excluded from sanitizer runs — the "
        "ASan/TSan runtime trips on XLA internals, not on our native core)",
    )
    config.addinivalue_line(
        "markers",
        "blockcache: needs POSIX shared memory AND UNIX-domain sockets "
        "(the host-shared decoded-block cache daemon, io/blockcache.py); "
        "skipped with a visible reason where either is unavailable",
    )


def _blockcache_unsupported():
    """Reason string when this host cannot run the shared block-cache
    daemon (no /dev/shm-backed POSIX shm, or no UNIX sockets — e.g.
    some containers and non-POSIX platforms); None when it can."""
    import socket
    import tempfile

    try:
        from dmlc_core_tpu.io.blockcache import _ShmSegment

        seg = _ShmSegment(f"dmlcprobe-{os.getpid()}", create=True, size=8)
        try:
            seg.buf[:2] = b"ok"
        finally:
            seg.close()
            seg.unlink()
    except Exception as e:
        return f"POSIX shared memory unavailable: {e!r}"
    try:
        with tempfile.TemporaryDirectory() as d:
            s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            try:
                s.bind(os.path.join(d, "probe.sock"))
            finally:
                s.close()
    except Exception as e:
        return f"UNIX-domain sockets unavailable: {e!r}"
    return None


def pytest_collection_modifyitems(config, items):
    reason = False  # tri-state: False = not probed yet
    for item in items:
        if item.get_closest_marker("blockcache") is None:
            continue
        if reason is False:
            reason = _blockcache_unsupported()
        if reason:
            import pytest

            item.add_marker(pytest.mark.skip(
                reason=f"shared block-cache daemon unsupported: {reason}"
            ))

# Tests never take the chip: a chip belongs to one process, and the
# driver runs the suite in several workers. The env default above pins
# the backend; the config update also covers a caller whose env names
# another platform. The chip is reached only through the benchmark,
# benchmarks/chip/run.py.
try:
    import jax

    jax.config.update("jax_platforms", "cpu")
except ImportError:  # data-layer-only environments
    pass


def install_fake_binary(tmp_path, monkeypatch, name, content):
    """Drop an executable stand-in (fake gcloud/ssh/srun) onto PATH —
    shared by the backend integration suites."""
    import os
    import stat

    bindir = tmp_path / "bin"
    bindir.mkdir(exist_ok=True)
    f = bindir / name
    f.write_text(content)
    f.chmod(f.stat().st_mode | stat.S_IXUSR)
    monkeypatch.setenv("PATH", f"{bindir}:{os.environ['PATH']}")
    return f

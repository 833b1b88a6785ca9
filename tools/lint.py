#!/usr/bin/env python
"""AST lint gate for the repo (reference scripts/lint.py via
travis_script.sh:19-23 — the reference runs a pylint-style pass per
commit; this is the dependency-free equivalent for this tree).

Checks (each finding is `path:line: code message`, exit 1 on any):
  L001 unused import            (name imported but never referenced;
                                 `__all__` strings and re-export aliases
                                 like `import x as x` count as uses)
  L002 bare except              (`except:` hides SystemExit/KeyboardInterrupt;
                                 use `except Exception:` at minimum)
  L003 mutable default argument (def f(x=[]) shares state across calls)
  L004 f-string without placeholders (usually a forgotten format arg)
  L005 duplicate dict key       (silently drops the earlier value)
  L006 direct urlopen           (all remote HTTP must ride the transient-
                                 failure retry layer; io/retry.py owns the
                                 single urlopen call site and is exempt)
  L007 direct jax.device_put    (all host→device transfers must ride the
                                 coalesced staging layer; dmlc_core_tpu/
                                 staging/ owns the call sites and is
                                 exempt, tests/ may build device fixtures,
                                 and link probes opt out per line with
                                 `# noqa: L007`. Non-batch placements go
                                 through staging.device_put.)
  L008 time.time() in dmlc_core_tpu/ (durations measured with the wall
                                 clock go backwards under NTP slew; use
                                 time.perf_counter()/monotonic() — the
                                 telemetry histograms assume it. Genuine
                                 wall-clock sites — token/JWT expiry in
                                 io/cloudfs.py, job timestamps in
                                 tracker/tracker.py — opt out per line
                                 with `# noqa: L008`.)
  L009 direct compression import (zlib/gzip/zstandard/lz4 belong to the
                                 codec layer: io/codec.py owns the one
                                 compression site — registry, levels,
                                 block header/crc, decode pool, decoded-
                                 block cache — and is exempt; everything
                                 else compresses through it so telemetry
                                 and import guards can't be bypassed)
  L010 raw socket import in dmlc_core_tpu/io/ (the two sanctioned wire
                                 services own the socket sites:
                                 io/blockcache.py — control-plane
                                 framing, lease bookkeeping — and
                                 io/lookup.py — the point-read serve
                                 daemon — are exempt; everything else
                                 in io/ rides their clients so the
                                 fallback semantics and io.blockcache.*
                                 telemetry can't be bypassed. Genuine
                                 non-cache uses — retry.py's socket
                                 exception classification — opt out per
                                 line with `# noqa: L010`.)
  L019 shared-memory segment construction outside io/shm.py (imports
                                 of _posixshmem or multiprocessing.
                                 shared_memory, and alias-aware
                                 shm_open/shm_unlink/SharedMemory
                                 calls, anywhere in dmlc_core_tpu/:
                                 ShmSegment in io/shm.py is the one
                                 construction site — it owns the
                                 no-resource-tracker rationale
                                 (bpo-39959), explicit unlink
                                 lifecycle and the SIGKILL leak
                                 trade-off; blockcache and the dsserve
                                 same-host transport both ride it.
                                 File-backed mmap — io/split.py,
                                 staging/fused.py — is out of scope.)
  L011 Chrome trace-event literal in dmlc_core_tpu/ (the flight
                                 recorder owns trace-event emission and
                                 the trace-file format:
                                 telemetry/tracing.py — event schema,
                                 clock rebasing, drop accounting, the
                                 traceEvents container — and is exempt;
                                 everything else records through its
                                 span/instant/counter API so per-thread
                                 ordering and drop counters can't be
                                 bypassed. Flags dict literals shaped
                                 like an event ({"ph": ..., "ts": ...})
                                 or like the file ({"traceEvents": ...});
                                 reading those keys from a loaded trace
                                 is fine.)
  L013 rendezvous cmd string literal in dmlc_core_tpu/tracker/ (the
                                 wire protocol's command strings —
                                 start/recover/shutdown/print/metrics/
                                 shard_lease/shard_renew/shard_done/
                                 shard_release/watch —
                                 are spelled out in exactly one place:
                                 tracker/protocol.py's CMD_* constants.
                                 A literal elsewhere in tracker/ can
                                 typo into an unknown-cmd drop the
                                 protocol check never catches; compare
                                 and send the constants. Tests crafting
                                 raw frames live outside the scope.)
  L014 raw socket construction in dmlc_core_tpu/tracker/ (the wire
                                 layer owns TCP plumbing: protocol.py —
                                 listeners via make_listener /
                                 bind_first_free / find_free_port,
                                 dials via connect_worker /
                                 connect_peer — and collective.py (the
                                 peer-link data plane) are exempt; a
                                 socket.socket( / create_connection(
                                 elsewhere in tracker/ forks timeout
                                 and error-handling policy per call
                                 site. Genuine non-wire uses — the UDP
                                 route probe in get_host_ip — opt out
                                 per line with `# noqa: L014`.)
  L015 struct frame pack/unpack in dmlc_core_tpu/dsserve/ and
                                 dmlc_core_tpu/tracker/ (binary wire
                                 framing is a single-site concern: the
                                 dsserve slot-frame header lives in
                                 dsserve/wire.py, the rendezvous int/
                                 string frames in tracker/protocol.py,
                                 the collective's peer-link header in
                                 tracker/collective.py — those three
                                 are exempt. A struct.pack/unpack/
                                 Struct call elsewhere in either tree
                                 hand-rolls a frame that can drift
                                 field order or endianness against the
                                 sanctioned sites and corrupt every
                                 frame after it.)
  L012 thread-pool creation in dmlc_core_tpu/io/ (exactly two pools are
                                 sanctioned: codec.py's decode pool —
                                 sized by the cgroup/affinity-aware
                                 usable-CPU count, DMLC_DECODE_THREADS —
                                 and spanfetch.py's ranged-fetch pool —
                                 DMLC_FETCH_THREADS + the in-flight
                                 byte budget. An ad-hoc
                                 ThreadPoolExecutor/ThreadPool anywhere
                                 else in io/ bypasses the cgroup-aware
                                 sizing and the budget; route decode
                                 work through codec's pool and remote
                                 reads through SpanFetcher.)
  L017 trace-context encode/decode outside telemetry/tracing.py (the
                                 causal RPC trace context — 16-hex-
                                 digit trace/span ids, "trace-span" on
                                 the wire — is encoded and decoded in
                                 exactly one module: tracing.py's
                                 encode_context/decode_context. A
                                 hand-rolled 016x format or base-16
                                 int parse elsewhere in the wire-
                                 speaking trees (telemetry/, tracker/,
                                 dsserve/, io/, tools/, staging/) can
                                 drift the format and silently break
                                 every flow arrow; carry the context
                                 as the opaque string tracing hands
                                 out.)
  L018 journal CRC record framing outside tracker/journal.py (the
                                 tracker's crash-recovery WAL — CRC-
                                 framed, torn-tail-truncating — is
                                 written and verified in exactly one
                                 module: journal.py. A binascii.crc32/
                                 zlib.crc32 call elsewhere in
                                 dmlc_core_tpu/tracker/ starts a
                                 second checksum site whose framing
                                 can drift against the replay path and
                                 turn a recoverable journal into one
                                 strict replay refuses.)
  L016 socket-serving request loops in dmlc_core_tpu/io/ (exactly two
                                 modules are sanctioned servers there:
                                 blockcache.py — the shared-cache
                                 control plane — and lookup.py — the
                                 point-read serve daemon. A listen/
                                 accept/create_server elsewhere in io/
                                 forks connection lifecycle, frame
                                 hygiene and teardown policy per call
                                 site; serve through those two or live
                                 outside io/.)
  L020 stream manifest literal / tail-frame walk in dmlc_core_tpu/
                                 (the streaming commit point is a
                                 single-site concern: stream/
                                 manifest.py owns the "manifest.json"
                                 filename (MANIFEST_NAME), the atomic-
                                 rename read/write pair, and the
                                 decode_length-driven frame walks —
                                 whole_record_prefix / walk_frames /
                                 scan_committed_prefix / count_records
                                 — that decide where a growing shard's
                                 committed prefix ends. A filename
                                 literal elsewhere can drift against
                                 the publisher; a second frame walk
                                 can disagree about where the torn
                                 tail starts and read uncommitted
                                 bytes. Spell the name via
                                 MANIFEST_NAME and walk frames through
                                 manifest.py's helpers; docstrings
                                 mentioning the filename are fine.)

Run: python tools/lint.py [paths...]   (default: the repo's source roots)
"""

from __future__ import annotations

import ast
import sys
from pathlib import Path
from typing import Iterator, List, Tuple

REPO = Path(__file__).resolve().parent.parent
DEFAULT_PATHS = [
    "dmlc_core_tpu",
    "tests",
    "benchmarks",
    "tools",
    "examples",
    "bench.py",
    "chip_smoke.py",
    "__graft_entry__.py",
]

Finding = Tuple[str, int, str, str]  # path, line, code, message


def _py_files(paths: List[str]) -> Iterator[Path]:
    for p in paths:
        path = (REPO / p) if not Path(p).is_absolute() else Path(p)
        if path.is_dir():
            yield from sorted(path.rglob("*.py"))
        elif path.suffix == ".py":
            yield path


def _names_loaded(tree: ast.AST) -> set:
    """Every identifier the module references outside import statements,
    plus attribute roots (`os.path` uses `os`) and `__all__` strings."""
    used = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            root = node
            while isinstance(root, ast.Attribute):
                root = root.value
            if isinstance(root, ast.Name):
                used.add(root.id)
        elif isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and tgt.id == "__all__":
                    for elt in ast.walk(node.value):
                        if isinstance(elt, ast.Constant) and isinstance(
                            elt.value, str
                        ):
                            used.add(elt.value)
    return used


def _check_unused_imports(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    used = _names_loaded(tree)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                bound = alias.asname or alias.name.partition(".")[0]
                if alias.asname == alias.name:
                    continue  # `import x as x` is a deliberate re-export
                if bound not in used:
                    yield node.lineno, f"unused import '{alias.name}'"
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue
            for alias in node.names:
                if alias.name == "*":
                    continue
                if alias.asname == alias.name:
                    continue  # PEP 484 re-export idiom
                bound = alias.asname or alias.name
                if bound not in used:
                    yield node.lineno, f"unused import '{alias.name}'"


def _check_bare_except(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            yield node.lineno, "bare 'except:' (catch Exception instead)"


def _check_mutable_defaults(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for default in list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None
            ]:
                if isinstance(default, (ast.List, ast.Dict, ast.Set)):
                    yield default.lineno, (
                        f"mutable default argument in '{node.name}()'"
                    )


def _check_fstring_no_placeholder(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    # a FormattedValue's format_spec is itself a JoinedStr (usually all
    # constants, e.g. the ".4f" in f"{x:.4f}") — not a reportable f-string
    specs = {
        id(node.format_spec)
        for node in ast.walk(tree)
        if isinstance(node, ast.FormattedValue) and node.format_spec is not None
    }
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.JoinedStr)
            and id(node) not in specs
            and not any(isinstance(v, ast.FormattedValue) for v in node.values)
        ):
            yield node.lineno, "f-string without placeholders"


def _check_duplicate_dict_keys(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    for node in ast.walk(tree):
        if isinstance(node, ast.Dict):
            seen = set()
            for key in node.keys:
                if isinstance(key, ast.Constant):
                    try:
                        hash(key.value)
                    except TypeError:
                        continue
                    if key.value in seen:
                        yield key.lineno, f"duplicate dict key {key.value!r}"
                    seen.add(key.value)


def _check_direct_urlopen(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    """Any call whose target is (or resolves to) urllib.request.urlopen.
    Catches ``urllib.request.urlopen(...)``, ``request.urlopen(...)``
    and a bare ``urlopen(...)`` bound by ``from urllib.request import
    urlopen`` (with or without an alias)."""
    aliases = {"urlopen"}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "urllib.request":
            for alias in node.names:
                if alias.name == "urlopen":
                    aliases.add(alias.asname or alias.name)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        hit = (isinstance(f, ast.Name) and f.id in aliases) or (
            isinstance(f, ast.Attribute) and f.attr == "urlopen"
        )
        if hit:
            yield node.lineno, (
                "direct urlopen call (route remote HTTP through the "
                "retry layer, io/retry.py)"
            )


def _check_direct_device_put(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    """Any call whose target resolves to jax's device_put: the staging
    layer (dmlc_core_tpu/staging/) owns every host→device transfer so
    batches always ride the coalesced single-DMA / packed-shard paths.
    Catches ``jax.device_put(...)``, any ``X.device_put(...)`` attribute
    call, and a bare ``device_put(...)`` bound by ``from jax import
    device_put`` (with or without an alias). The staging layer's own
    ``device_put`` wrapper imported as a bare name is NOT flagged — that
    wrapper is the sanctioned escape hatch for non-batch placements."""
    aliases = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "jax":
            for alias in node.names:
                if alias.name == "device_put":
                    aliases.add(alias.asname or alias.name)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        hit = (isinstance(f, ast.Name) and f.id in aliases) or (
            isinstance(f, ast.Attribute) and f.attr == "device_put"
        )
        if hit:
            yield node.lineno, (
                "direct device_put call (host→device transfers belong to "
                "the staging layer; import staging.device_put for "
                "non-batch placements)"
            )


def _check_wall_clock_time(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    """Any call resolving to ``time.time``: the ``<mod>.time(...)``
    attribute call where ``<mod>`` is the time module under any name
    (``import time`` / ``import time as t``) and a bare ``time(...)``
    bound by ``from time import time`` (with or without an alias).
    Scoped to dmlc_core_tpu/ (see lint_file): library code measuring
    durations must use perf_counter/monotonic; legitimate wall-clock
    reads opt out per line with ``# noqa: L008``."""
    fn_aliases = set()
    mod_aliases = {"time"}  # names the time MODULE is bound to
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "time":
            for alias in node.names:
                if alias.name == "time":
                    fn_aliases.add(alias.asname or alias.name)
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "time":
                    mod_aliases.add(alias.asname or alias.name)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        hit = (isinstance(f, ast.Name) and f.id in fn_aliases) or (
            isinstance(f, ast.Attribute)
            and f.attr == "time"
            and isinstance(f.value, ast.Name)
            and f.value.id in mod_aliases
        )
        if hit:
            yield node.lineno, (
                "time.time() for measurement (use time.perf_counter()/"
                "monotonic(); wall-clock sites opt out with noqa: L008)"
            )


_CODEC_MODULES = ("zlib", "gzip", "zstandard", "lz4")


def _check_codec_imports(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    """Any import binding a compression module (zlib/gzip/zstandard/lz4,
    incl. submodules like lz4.frame): compression is one layer
    (io/codec.py — codec registry, block header + crc, decode pool,
    decoded-block cache, telemetry), mirroring the L006 (urlopen) and
    L008 (time.time) single-site pattern."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                root = alias.name.partition(".")[0]
                if root in _CODEC_MODULES:
                    yield node.lineno, (
                        f"direct import of {alias.name!r} (compression "
                        f"belongs to the codec layer, io/codec.py)"
                    )
        elif isinstance(node, ast.ImportFrom):
            root = (node.module or "").partition(".")[0]
            if node.level == 0 and root in _CODEC_MODULES:
                yield node.lineno, (
                    f"direct import from {node.module!r} (compression "
                    f"belongs to the codec layer, io/codec.py)"
                )


# files allowed to call urlopen directly: the retry layer itself (the
# leading '/' anchors the path segment — audio/retry.py is NOT exempt)
_L006_EXEMPT = ("/io/retry.py",)
# files allowed to import compression modules directly: the codec layer
_L009_EXEMPT = ("/io/codec.py",)
# L010 is SCOPED to dmlc_core_tpu/io/ and exempts the two sanctioned
# wire services: the block-cache daemon (UNIX-socket control plane) and
# the point-read serve daemon (TCP request loop, io/lookup.py)
_L010_SCOPE_DIRS = ("dmlc_core_tpu/io/",)
_L010_EXEMPT = ("/io/blockcache.py", "/io/lookup.py")
# L019 is scoped to the WHOLE library (a shm segment could plausibly be
# minted anywhere) and exempts the one sanctioned construction site
_L019_SCOPE_DIRS = ("dmlc_core_tpu/",)
_L019_EXEMPT = ("/io/shm.py",)
# L020 is scoped to the WHOLE library (a manifest path could be spelled
# anywhere a stream is opened) and exempts the one sanctioned site for
# the filename, the atomic read/write pair and the tail-frame walks.
# recordio.py DEFINES decode_length — definitions aren't imports, so it
# needs no exemption.
_L020_SCOPE_DIRS = ("dmlc_core_tpu/",)
_L020_EXEMPT = ("/stream/manifest.py",)
# L016 is scoped to dmlc_core_tpu/io/ and exempts the same two files —
# the only modules allowed to RUN a socket-serving request loop there
_L016_SCOPE_DIRS = ("dmlc_core_tpu/io/",)
_L016_EXEMPT = ("/io/blockcache.py", "/io/lookup.py")
# trees allowed to call jax.device_put directly: the staging layer owns
# the transfer call sites; tests build device-resident fixtures.
# Anchored against the REPO-RELATIVE path (a checkout living under e.g.
# /home/ci/tests/ must not exempt the whole repo); files outside the
# repo (lint_file called on scratch dirs, as the lint's own tests do)
# fall back to an absolute-path segment match.
_L007_EXEMPT_DIRS = ("dmlc_core_tpu/staging/", "tests/")
# L008 is SCOPED (not exempted): it only applies to library code under
# dmlc_core_tpu/ — benches and tests measure with perf_counter already,
# and scripts outside the library may legitimately want wall-clock
_L008_SCOPE_DIRS = ("dmlc_core_tpu/",)
# L011 is scoped to dmlc_core_tpu/ and exempts the flight recorder,
# which owns trace-event emission and the trace-file format
_L011_SCOPE_DIRS = ("dmlc_core_tpu/",)
_L011_EXEMPT = ("/telemetry/tracing.py",)
# L012 is scoped to dmlc_core_tpu/io/ and exempts the two sanctioned
# pool owners: the codec decode pool and the span-fetch pool
_L012_SCOPE_DIRS = ("dmlc_core_tpu/io/",)
_L012_EXEMPT = ("/io/codec.py", "/io/spanfetch.py")
# L013 is scoped to dmlc_core_tpu/tracker/ and exempts the protocol
# module, which owns the CMD_* constants. Kept in sync with
# protocol.RENDEZVOUS_CMDS by a test (tests/test_lint.py).
_L013_SCOPE_DIRS = ("dmlc_core_tpu/tracker/",)
_L013_EXEMPT = ("/tracker/protocol.py",)
# L014 is scoped to dmlc_core_tpu/tracker/ and exempts the two
# sanctioned wire modules: protocol.py (listeners + dials) and
# collective.py (the peer-link data plane)
_L014_SCOPE_DIRS = ("dmlc_core_tpu/tracker/",)
_L014_EXEMPT = ("/tracker/protocol.py", "/tracker/collective.py")
# L015 is scoped to the two trees that own binary wire protocols and
# exempts their sanctioned frame sites (the dsserve slot framing, the
# rendezvous int/string framing, the collective peer-link header)
_L015_SCOPE_DIRS = ("dmlc_core_tpu/dsserve/", "dmlc_core_tpu/tracker/")
_L015_EXEMPT = (
    "/dsserve/wire.py",
    "/tracker/protocol.py",
    "/tracker/collective.py",
    "/tracker/journal.py",
)
# L018 is scoped to dmlc_core_tpu/tracker/ and exempts the journal,
# which owns the WAL's CRC record framing (write AND verify sides)
_L018_SCOPE_DIRS = ("dmlc_core_tpu/tracker/",)
_L018_EXEMPT = ("/tracker/journal.py",)
# L017 is scoped to the wire-speaking trees (everywhere a trace
# context could plausibly be hand-rolled onto a protocol) and exempts
# the flight recorder, which owns the context encoding
_L017_SCOPE_DIRS = (
    "dmlc_core_tpu/telemetry/",
    "dmlc_core_tpu/tracker/",
    "dmlc_core_tpu/dsserve/",
    "dmlc_core_tpu/io/",
    "dmlc_core_tpu/tools/",
    "dmlc_core_tpu/staging/",
)
_L017_EXEMPT = ("/telemetry/tracing.py",)
_L013_CMDS = frozenset(
    {
        "start",
        "recover",
        "shutdown",
        "print",
        "metrics",
        "shard_lease",
        "shard_renew",
        "shard_done",
        "shard_release",
        "watch",
    }
)


def _check_rendezvous_cmd_literals(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    """Any string constant spelling a rendezvous command: inside
    dmlc_core_tpu/tracker/ the wire command vocabulary lives in
    protocol.py's ``CMD_*`` constants (single-site pattern of
    L006/L008-L012) — a literal comparison or send elsewhere can typo
    into a silently-dropped unknown command. Scoped in lint_file;
    docstrings match only if the ENTIRE docstring is a command word,
    which no real docstring is."""
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Constant)
            and isinstance(node.value, str)
            and node.value in _L013_CMDS
        ):
            yield node.lineno, (
                f"rendezvous cmd literal {node.value!r} (compare/send the "
                "CMD_* constants from tracker/protocol.py)"
            )

def _check_shm_socket_imports(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    """Any import binding the ``socket`` module: inside
    dmlc_core_tpu/io/ the two sanctioned wire services (io/blockcache.py
    — UNIX-socket control plane — and io/lookup.py — the point-read
    serve daemon) own cross-process traffic, mirroring the
    L006/L008/L009 single-site pattern. Shared-memory construction is
    L019's business (io/shm.py). Scoped in lint_file."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.partition(".")[0] == "socket":
                    yield node.lineno, (
                        "direct socket import in io/ (cross-process "
                        "traffic belongs to io/blockcache.py and "
                        "io/lookup.py)"
                    )
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            mod = node.module or ""
            if mod.partition(".")[0] == "socket":
                yield node.lineno, (
                    "direct socket import in io/ (cross-process "
                    "traffic belongs to io/blockcache.py and "
                    "io/lookup.py)"
                )


_SHM_CTORS = ("shm_open", "shm_unlink", "SharedMemory")


def _check_shm_segment_construction(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    """Any import binding ``_posixshmem`` or ``multiprocessing.
    shared_memory`` (incl. ``from multiprocessing import
    shared_memory`` / ``from multiprocessing.shared_memory import
    SharedMemory``), and any call resolving to ``shm_open`` /
    ``shm_unlink`` / ``SharedMemory`` under any alias: inside
    dmlc_core_tpu/ shared-memory segment construction is one module —
    io/shm.py's ShmSegment, which owns the no-resource-tracker
    rationale (bpo-39959), the explicit create/attach/unlink lifecycle
    and the leak trade-off — mirroring the L006/L008-L018 single-site
    pattern. A second construction site forks segment naming and
    lifecycle policy; blockcache leases and the dsserve same-host
    transport both ride ShmSegment. File-backed ``mmap`` (io/split.py,
    staging/fused.py) is NOT this rule's business. Scoped in
    lint_file."""
    fn_aliases = set()
    mod_aliases = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.partition(".")[0] == "_posixshmem":
                    yield node.lineno, (
                        "direct _posixshmem import (segment construction "
                        "belongs to io/shm.py's ShmSegment)"
                    )
                    mod_aliases.add(alias.asname or "_posixshmem")
                elif alias.name.startswith("multiprocessing.shared_memory"):
                    yield node.lineno, (
                        "direct shared_memory import (shared segments "
                        "belong to io/shm.py's ShmSegment)"
                    )
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            mod = node.module or ""
            if mod.partition(".")[0] == "_posixshmem":
                yield node.lineno, (
                    "direct _posixshmem import (segment construction "
                    "belongs to io/shm.py's ShmSegment)"
                )
                for alias in node.names:
                    if alias.name in _SHM_CTORS:
                        fn_aliases.add(alias.asname or alias.name)
            elif mod.startswith("multiprocessing.shared_memory") or (
                mod == "multiprocessing"
                and any(a.name == "shared_memory" for a in node.names)
            ):
                yield node.lineno, (
                    "direct shared_memory import (shared segments "
                    "belong to io/shm.py's ShmSegment)"
                )
                for alias in node.names:
                    if alias.name in _SHM_CTORS:
                        fn_aliases.add(alias.asname or alias.name)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        hit = (
            isinstance(f, ast.Name)
            and (f.id in fn_aliases or f.id in ("shm_open", "shm_unlink"))
        ) or (
            isinstance(f, ast.Attribute)
            and (
                f.attr in ("shm_open", "shm_unlink")
                or (
                    f.attr == "SharedMemory"
                    and isinstance(f.value, ast.Name)
                    and f.value.id in mod_aliases | {"shared_memory"}
                )
            )
        )
        if hit:
            yield node.lineno, (
                "shared-memory segment construction outside io/shm.py "
                "(shm_open/shm_unlink/SharedMemory belong to ShmSegment "
                "— a second site forks naming and lifecycle policy)"
            )


def _check_trace_event_literals(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    """Dict literals shaped like a Chrome trace event (both ``"ph"``
    and ``"ts"`` constant keys) or like the trace-file container (a
    ``"traceEvents"`` constant key): the flight recorder
    (telemetry/tracing.py) owns the event schema and the file format,
    mirroring the L006/L008-L010 single-site pattern — ad-hoc event
    dicts would fork the clock rebasing and dodge the ring's drop
    accounting. Scoped to dmlc_core_tpu/ in lint_file; reading those
    keys from a loaded trace (subscripts, .get) is not flagged."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Dict):
            continue
        keys = {
            k.value
            for k in node.keys
            if isinstance(k, ast.Constant) and isinstance(k.value, str)
        }
        if "traceEvents" in keys:
            yield node.lineno, (
                "trace-file dict literal (the traceEvents container "
                "belongs to telemetry/tracing.py)"
            )
        elif "ph" in keys and "ts" in keys:
            yield node.lineno, (
                "Chrome trace-event dict literal (record through the "
                "flight-recorder API, telemetry/tracing.py)"
            )


_POOL_TYPES = ("ThreadPoolExecutor", "ProcessPoolExecutor", "ThreadPool")


def _check_thread_pool_creation(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    """Any call instantiating an executor/pool type (``ThreadPool
    Executor(...)``, ``futures.ThreadPoolExecutor(...)``,
    ``multiprocessing.pool.ThreadPool(...)`` — with or without an
    import alias): inside dmlc_core_tpu/io/ exactly two pools are
    sanctioned — codec.py's decode pool and spanfetch.py's ranged-fetch
    pool, both sized from the cgroup/affinity-aware usable-CPU count
    with documented env overrides. Scoped in lint_file; everything else
    in io/ must ride those so the sizing policy and the span fetcher's
    in-flight byte budget cannot be bypassed."""
    aliases = set(_POOL_TYPES)
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            for alias in node.names:
                if alias.name in _POOL_TYPES and alias.asname:
                    aliases.add(alias.asname)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        hit = (isinstance(f, ast.Name) and f.id in aliases) or (
            isinstance(f, ast.Attribute) and f.attr in _POOL_TYPES
        )
        if hit:
            yield node.lineno, (
                "thread-pool creation in io/ (the decode pool in "
                "io/codec.py and the span-fetch pool in io/spanfetch.py "
                "are the sanctioned executors — ad-hoc pools bypass the "
                "cgroup-aware sizing and the in-flight byte budget)"
            )


_SOCKET_CTORS = ("socket", "create_connection")


def _check_socket_construction(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    """Any call constructing a TCP socket — ``socket.socket(...)`` /
    ``socket.create_connection(...)`` under any module alias, or the
    bare names bound by ``from socket import socket/create_connection``
    (with or without an alias): inside dmlc_core_tpu/tracker/ the wire
    layer is one place (protocol.py's make_listener / bind_first_free /
    find_free_port / connect_worker / connect_peer, and collective.py's
    peer-link data plane), mirroring the L006/L008-L013 single-site
    pattern — an ad-hoc socket forks connect/IO-timeout policy and
    error handling per call site. Scoped in lint_file; the UDP route
    probe opts out per line with ``# noqa: L014``."""
    fn_aliases = set()
    mod_aliases = {"socket"}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "socket":
            for alias in node.names:
                if alias.name in _SOCKET_CTORS:
                    fn_aliases.add(alias.asname or alias.name)
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "socket":
                    mod_aliases.add(alias.asname or "socket")
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        hit = (isinstance(f, ast.Name) and f.id in fn_aliases) or (
            isinstance(f, ast.Attribute)
            and f.attr in _SOCKET_CTORS
            and isinstance(f.value, ast.Name)
            and f.value.id in mod_aliases
        )
        if hit:
            yield node.lineno, (
                "raw socket construction in tracker/ (listeners/dials "
                "belong to tracker/protocol.py — make_listener, "
                "bind_first_free, find_free_port, connect_worker, "
                "connect_peer)"
            )


_STRUCT_FNS = ("pack", "unpack", "pack_into", "unpack_from", "Struct")


def _check_struct_framing(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    """Any call resolving to the struct module's pack/unpack/Struct —
    ``struct.pack(...)`` under any module alias, or the bare names
    bound by ``from struct import pack/Struct`` (with or without an
    alias): inside dmlc_core_tpu/dsserve/ and dmlc_core_tpu/tracker/
    the wire framing is a single-site concern (dsserve/wire.py's slot
    frames, protocol.py's int/string frames, collective.py's peer-link
    header), mirroring the L006/L008-L014 pattern — a second
    hand-rolled frame site can drift field order or endianness and
    corrupt every frame after it. Scoped in lint_file."""
    fn_aliases = set()
    mod_aliases = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "struct":
            for alias in node.names:
                if alias.name in _STRUCT_FNS:
                    fn_aliases.add(alias.asname or alias.name)
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "struct":
                    mod_aliases.add(alias.asname or "struct")
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        hit = (isinstance(f, ast.Name) and f.id in fn_aliases) or (
            isinstance(f, ast.Attribute)
            and f.attr in _STRUCT_FNS
            and isinstance(f.value, ast.Name)
            and f.value.id in mod_aliases
        )
        if hit:
            yield node.lineno, (
                "struct frame pack/unpack outside the sanctioned wire "
                "modules (dsserve frames belong to dsserve/wire.py; "
                "tracker frames to protocol.py/collective.py)"
            )


def _check_socket_serving_loops(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    """Any call that makes a module a socket SERVER — ``.listen(...)``
    or ``.accept(...)`` on any object, or ``socket.create_server(...)``
    under any module alias (incl. the bare name bound by ``from socket
    import create_server``): inside dmlc_core_tpu/io/ exactly two
    request loops are sanctioned — the block-cache control plane
    (io/blockcache.py) and the point-read serve daemon (io/lookup.py),
    the L006/L008-L015 single-site pattern. Scoped in lint_file.
    Dialing out (connect/create_connection) is L010's business, not
    this rule's."""
    fn_aliases = set()
    mod_aliases = {"socket"}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "socket":
            for alias in node.names:
                if alias.name == "create_server":
                    fn_aliases.add(alias.asname or alias.name)
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "socket":
                    mod_aliases.add(alias.asname or "socket")
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        hit = (isinstance(f, ast.Name) and f.id in fn_aliases) or (
            isinstance(f, ast.Attribute)
            and (
                f.attr in ("accept", "listen")
                or (
                    f.attr == "create_server"
                    and isinstance(f.value, ast.Name)
                    and f.value.id in mod_aliases
                )
            )
        )
        if hit:
            yield node.lineno, (
                "socket-serving request loop in io/ (servers there are "
                "confined to io/blockcache.py and io/lookup.py — a "
                "third loop forks connection lifecycle and frame "
                "hygiene per site)"
            )


def _check_trace_context_codec(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    """Trace-context encode/decode primitives outside their owner:
    the 16-hex-digit id format spec (any string literal containing the
    marker, covering f-strings, %-format and str.format alike — the
    spec constant of an f-string IS a string literal in the AST) and
    base-16 ``int(x, 16)`` parsing. Both are how a module would
    hand-roll telemetry/tracing.py's encode_context/decode_context;
    alias games don't apply (``int`` is a builtin, the format marker is
    a literal), so the two patterns are the whole surface. Scoped in
    lint_file; tracing.py itself is exempt."""
    hex16 = "016" + "x"  # not spelled whole, or this file flags itself
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Constant)
            and isinstance(node.value, str)
            and hex16 in node.value
        ):
            yield node.lineno, (
                "16-hex-digit trace-id formatting outside "
                "telemetry/tracing.py (use tracing.encode_context / "
                "rpc_context and carry the string opaquely)"
            )
        elif isinstance(node, ast.Call) and (
            isinstance(node.func, ast.Name) and node.func.id == "int"
        ):
            base = None
            if len(node.args) >= 2 and isinstance(node.args[1], ast.Constant):
                base = node.args[1].value
            for kw in node.keywords:
                if kw.arg == "base" and isinstance(kw.value, ast.Constant):
                    base = kw.value.value
            if base == 16:
                yield node.lineno, (
                    "base-16 id parsing outside telemetry/tracing.py "
                    "(use tracing.decode_context; a second parser can "
                    "drift the wire format and silently break every "
                    "flow arrow)"
                )


_CRC_MODULES = ("binascii", "zlib")


def _check_journal_crc_framing(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    """Any call resolving to a crc32 — ``binascii.crc32(...)`` /
    ``zlib.crc32(...)`` under any module alias, or the bare name bound
    by ``from binascii import crc32`` (with or without an alias):
    inside dmlc_core_tpu/tracker/ the crash-recovery WAL's CRC record
    framing is a single-site concern (tracker/journal.py — the writer
    AND the strict/lenient readers), mirroring the L006/L008-L017
    pattern. A second checksum site can frame records the replay
    cannot verify — corruption indistinguishable from a real torn
    tail. Scoped in lint_file."""
    fn_aliases = set()
    mod_aliases = set(_CRC_MODULES)
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module in _CRC_MODULES:
            for alias in node.names:
                if alias.name == "crc32":
                    fn_aliases.add(alias.asname or alias.name)
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name in _CRC_MODULES:
                    mod_aliases.add(alias.asname or alias.name)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        hit = (isinstance(f, ast.Name) and f.id in fn_aliases) or (
            isinstance(f, ast.Attribute)
            and f.attr == "crc32"
            and isinstance(f.value, ast.Name)
            and f.value.id in mod_aliases
        )
        if hit:
            yield node.lineno, (
                "journal CRC record framing outside tracker/journal.py "
                "(the WAL's checksum write/verify is confined there — "
                "a second crc32 site can drift the frame format "
                "against the replay path)"
            )


_MANIFEST_NAME = "manifest.json"


def _docstring_consts(tree: ast.Module) -> set:
    """id()s of the Constant nodes that are module/class/function
    docstrings — prose ABOUT the manifest is not a second spelling of
    its path."""
    out = set()
    for node in ast.walk(tree):
        if isinstance(
            node,
            (ast.Module, ast.ClassDef, ast.FunctionDef, ast.AsyncFunctionDef),
        ):
            body = getattr(node, "body", [])
            if (
                body
                and isinstance(body[0], ast.Expr)
                and isinstance(body[0].value, ast.Constant)
                and isinstance(body[0].value.value, str)
            ):
                out.add(id(body[0].value))
    return out


def _check_stream_manifest_framing(tree: ast.Module) -> Iterator[Tuple[int, str]]:
    """Two spellings of the streaming commit point outside stream/
    manifest.py, mirroring the L006/L008-L019 single-site pattern:
    (a) a ``"manifest.json"`` string literal (incl. f-string parts) —
    hand-rolled manifest paths bypass the atomic-rename publisher and
    can drift the filename; import ``MANIFEST_NAME`` instead (the
    imported constant is the sanctioned alias and never flags); and
    (b) any import or alias-aware use of ``decode_length`` from the
    recordio module — the lrec length accessor only matters to a frame
    WALK (advance = 8 + pad4(length)), and tail-frame walks that
    decide where a growing shard's committed prefix ends live in
    manifest.py (whole_record_prefix / walk_frames /
    scan_committed_prefix / count_records). Sniffing a frame's FLAG
    (staging/fused.py's compression probe) doesn't need the length and
    stays quiet. Docstrings are ignored. Scoped in lint_file."""
    doc_ids = _docstring_consts(tree)
    lit_msg = (
        'stream manifest filename literal (the commit-point path is '
        "spelled once, stream/manifest.py's MANIFEST_NAME — a second "
        "spelling can drift against the atomic-rename publisher)"
    )
    walk_msg = (
        "RecordIO tail-frame walking outside stream/manifest.py "
        "(decode_length-driven walks decide where the committed "
        "prefix ends; a second walk can disagree about the torn "
        "tail and read uncommitted bytes — use manifest.py's "
        "whole_record_prefix/walk_frames/count_records)"
    )
    fn_aliases = set()
    mod_aliases = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod.rpartition(".")[2] == "recordio":
                for alias in node.names:
                    if alias.name == "decode_length":
                        yield node.lineno, walk_msg
                        fn_aliases.add(alias.asname or alias.name)
            elif any(a.name == "recordio" for a in node.names):
                for alias in node.names:
                    if alias.name == "recordio":
                        mod_aliases.add(alias.asname or alias.name)
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.rpartition(".")[2] == "recordio":
                    mod_aliases.add(
                        alias.asname or alias.name.partition(".")[0]
                    )
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant):
            if (
                isinstance(node.value, str)
                and _MANIFEST_NAME in node.value
                and id(node) not in doc_ids
            ):
                yield node.lineno, lit_msg
        elif isinstance(node, ast.Call):
            f = node.func
            if (isinstance(f, ast.Name) and f.id in fn_aliases) or (
                isinstance(f, ast.Attribute)
                and f.attr == "decode_length"
                and isinstance(f.value, ast.Name)
                and f.value.id in mod_aliases
            ):
                yield node.lineno, walk_msg


CHECKS = [
    ("L001", _check_unused_imports),
    ("L002", _check_bare_except),
    ("L003", _check_mutable_defaults),
    ("L004", _check_fstring_no_placeholder),
    ("L005", _check_duplicate_dict_keys),
    ("L006", _check_direct_urlopen),
    ("L007", _check_direct_device_put),
    ("L008", _check_wall_clock_time),
    ("L009", _check_codec_imports),
    ("L010", _check_shm_socket_imports),
    ("L011", _check_trace_event_literals),
    ("L012", _check_thread_pool_creation),
    ("L013", _check_rendezvous_cmd_literals),
    ("L014", _check_socket_construction),
    ("L015", _check_struct_framing),
    ("L016", _check_socket_serving_loops),
    ("L017", _check_trace_context_codec),
    ("L018", _check_journal_crc_framing),
    ("L019", _check_shm_segment_construction),
    ("L020", _check_stream_manifest_framing),
]


def lint_file(path: Path) -> List[Finding]:
    src = path.read_text()
    try:
        tree = ast.parse(src, filename=str(path))
    except SyntaxError as exc:  # compileall also catches this; belt+braces
        return [(str(path), exc.lineno or 0, "L000", f"syntax error: {exc.msg}")]
    # `# noqa` on a statement's first line suppresses its findings
    # (flake8 convention; re-export blocks carry `# noqa: F401`)
    noqa_lines = {
        i
        for i, text in enumerate(src.splitlines(), start=1)
        if "# noqa" in text
    }
    out: List[Finding] = []
    rel = str(path.relative_to(REPO)) if path.is_relative_to(REPO) else str(path)
    posix = path.as_posix()
    in_repo = path.is_relative_to(REPO)
    rel_posix = rel.replace("\\", "/") if in_repo else None
    for code, fn in CHECKS:
        if code == "L006" and posix.endswith(_L006_EXEMPT):
            continue
        if code == "L009" and posix.endswith(_L009_EXEMPT):
            continue
        if code == "L007" and (
            rel_posix.startswith(_L007_EXEMPT_DIRS)
            if in_repo
            else any("/" + d in posix for d in _L007_EXEMPT_DIRS)
        ):
            continue
        if code == "L008" and not (
            rel_posix.startswith(_L008_SCOPE_DIRS)
            if in_repo
            else any("/" + d in posix for d in _L008_SCOPE_DIRS)
        ):
            continue
        if code == "L010":
            if posix.endswith(_L010_EXEMPT):
                continue
            if not (
                rel_posix.startswith(_L010_SCOPE_DIRS)
                if in_repo
                else any("/" + d in posix for d in _L010_SCOPE_DIRS)
            ):
                continue
        if code == "L011":
            if posix.endswith(_L011_EXEMPT):
                continue
            if not (
                rel_posix.startswith(_L011_SCOPE_DIRS)
                if in_repo
                else any("/" + d in posix for d in _L011_SCOPE_DIRS)
            ):
                continue
        if code == "L012":
            if posix.endswith(_L012_EXEMPT):
                continue
            if not (
                rel_posix.startswith(_L012_SCOPE_DIRS)
                if in_repo
                else any("/" + d in posix for d in _L012_SCOPE_DIRS)
            ):
                continue
        if code == "L013":
            if posix.endswith(_L013_EXEMPT):
                continue
            if not (
                rel_posix.startswith(_L013_SCOPE_DIRS)
                if in_repo
                else any("/" + d in posix for d in _L013_SCOPE_DIRS)
            ):
                continue
        if code == "L014":
            if posix.endswith(_L014_EXEMPT):
                continue
            if not (
                rel_posix.startswith(_L014_SCOPE_DIRS)
                if in_repo
                else any("/" + d in posix for d in _L014_SCOPE_DIRS)
            ):
                continue
        if code == "L015":
            if posix.endswith(_L015_EXEMPT):
                continue
            if not (
                rel_posix.startswith(_L015_SCOPE_DIRS)
                if in_repo
                else any("/" + d in posix for d in _L015_SCOPE_DIRS)
            ):
                continue
        if code == "L016":
            if posix.endswith(_L016_EXEMPT):
                continue
            if not (
                rel_posix.startswith(_L016_SCOPE_DIRS)
                if in_repo
                else any("/" + d in posix for d in _L016_SCOPE_DIRS)
            ):
                continue
        if code == "L017":
            if posix.endswith(_L017_EXEMPT):
                continue
            if not (
                rel_posix.startswith(_L017_SCOPE_DIRS)
                if in_repo
                else any("/" + d in posix for d in _L017_SCOPE_DIRS)
            ):
                continue
        if code == "L018":
            if posix.endswith(_L018_EXEMPT):
                continue
            if not (
                rel_posix.startswith(_L018_SCOPE_DIRS)
                if in_repo
                else any("/" + d in posix for d in _L018_SCOPE_DIRS)
            ):
                continue
        if code == "L019":
            if posix.endswith(_L019_EXEMPT):
                continue
            if not (
                rel_posix.startswith(_L019_SCOPE_DIRS)
                if in_repo
                else any("/" + d in posix for d in _L019_SCOPE_DIRS)
            ):
                continue
        if code == "L020":
            if posix.endswith(_L020_EXEMPT):
                continue
            if not (
                rel_posix.startswith(_L020_SCOPE_DIRS)
                if in_repo
                else any("/" + d in posix for d in _L020_SCOPE_DIRS)
            ):
                continue
        for line, msg in fn(tree):
            if line not in noqa_lines:
                out.append((rel, line, code, msg))
    return out


def main(argv: List[str]) -> int:
    paths = argv or DEFAULT_PATHS
    findings: List[Finding] = []
    n_files = 0
    for f in _py_files(paths):
        if "__pycache__" in f.parts:
            continue
        n_files += 1
        findings.extend(lint_file(f))
    findings.sort()
    for path, line, code, msg in findings:
        print(f"{path}:{line}: {code} {msg}")
    print(
        f"lint: {n_files} files, {len(findings)} finding(s)",
        file=sys.stderr,
    )
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

"""Process-parallel cluster runtime — real daemons, real ``kill -9``.

The threaded ``MiniCluster`` runs every daemon inside one interpreter,
so "power loss" is a simulation (truncate + cold remount) and a knee
measurement measures the GIL.  This module is the other half: a daemon
described by a serializable :class:`DaemonSpec` is spawned as its own
OS process (``python -m ceph_tpu.procs <spec.json>``), joins the
cluster over the existing TCP messenger, and can be killed with a
genuine SIGKILL — nothing in the dead process gets a chance to flush,
truncate, or tidy up.  The parent talks to it only through what real
operators have: the wire, the admin socket (a Unix socket, so it
crosses the process boundary), the readiness file, and signals.

Contracts:

- **Boot spec**: everything a child needs rides one JSON blob —
  entity kind + ident, the monmap (ports pre-allocated by the
  parent), the WAL path, osd_config, the fault seed, and pre-assigned
  asok/readiness paths.  No pickling, no inherited Python state.
- **Readiness**: the child writes ``{"pid", "ident"}`` atomically to
  ``spec.ready_path`` only once the daemon is actually serving (an
  OSD after ``start(wait_for_up=True)`` returns).  ``spawn_daemon``
  polls ready-file vs process-exit vs deadline, and retries a failed
  spawn before raising :class:`ProcSpawnError` with the log tail.
- **Orphan reaping**: every spawn registers in a module-level PID
  table; ``reap_orphans()`` SIGKILLs + waits anything still alive and
  runs from ``atexit`` always — a crashed test cannot strand daemons.
  ``tests/conftest.py`` additionally asserts the table is empty at
  session teardown so a leak fails the run loudly.
- **One process per chip**: procs-mode daemons always run their
  batch-engine lanes on the CPU — ``spawn_daemon`` sets
  ``JAX_PLATFORMS=cpu`` in every child's environment.  A chip belongs
  to one process at a time, and the parent of a procs cluster may
  already hold it; a child that tried to claim it would fail or hang.
- **kill -9 semantics**: children run with ``CEPH_TPU_PROC_DAEMON=1``
  in the environment, which arms the ``kill9`` crash point in
  ``WALStore`` to deliver a real ``os.kill(getpid(), SIGKILL)``.
  Because the store flushes the WAL per append, the OS page cache
  holds every appended record at the instant of death — SIGKILL
  loses *process* state, not *written* state — while a simulated
  power cut keeps only the fsynced prefix.  Both are one revive away:
  a fresh process cold-remounts the same WAL file.
"""

from __future__ import annotations

import atexit
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field

DAEMON_KINDS = ("mon", "osd", "mgr", "msgr_victim")

# child-process marker: WALStore's kill9 crash point delivers a real
# SIGKILL only when this is set (threaded mode degrades it to the
# pre_append simulated power cut)
PROC_ENV = "CEPH_TPU_PROC_DAEMON"


class ProcSpawnError(RuntimeError):
    """A daemon process failed to come up within its retry budget."""


# -- orphan registry ------------------------------------------------------
# pid → ProcHandle for every child THIS process spawned.  The atexit
# sweep is the backstop; conftest.py's session fixture is the loud
# version that fails the test run on a leak.
_SPAWNED: dict[int, "ProcHandle"] = {}
_REG_LOCK = threading.Lock()


def register_pid(handle: "ProcHandle") -> None:
    with _REG_LOCK:
        _SPAWNED[handle.pid] = handle


def unregister_pid(pid: int) -> None:
    with _REG_LOCK:
        _SPAWNED.pop(pid, None)


def live_pids() -> list[int]:
    """PIDs of spawned children still alive (reaps exited ones)."""
    with _REG_LOCK:
        handles = list(_SPAWNED.values())
    return [h.pid for h in handles if h.alive()]


def reap_orphans() -> list[int]:
    """SIGKILL + wait every tracked child still alive; → reaped PIDs."""
    reaped = []
    with _REG_LOCK:
        handles = list(_SPAWNED.values())
        _SPAWNED.clear()
    for h in handles:
        if h.alive():
            reaped.append(h.pid)
            try:
                h.proc.kill()
            except OSError:
                pass
        try:
            h.proc.wait(timeout=10)
        except Exception:   # noqa: BLE001 — best-effort at teardown
            pass
    return reaped


atexit.register(reap_orphans)


# -- boot spec ------------------------------------------------------------
@dataclass
class DaemonSpec:
    """Serializable boot description for one daemon process."""

    kind: str                        # one of DAEMON_KINDS
    ident: str                       # "0" for mon.0/osd.0, mgr name …
    monmap: dict | None = None       # MonMap.to_dict()
    wal_path: str | None = None      # OSD: durable backing (walstore)
    osd_config: dict = field(default_factory=dict)
    fault_seed: int | None = None
    asok_path: str | None = None     # pre-assigned admin socket
    ready_path: str | None = None    # readiness-file handshake
    extra: dict = field(default_factory=dict)   # kind-specific knobs

    def __post_init__(self):
        if self.kind not in DAEMON_KINDS:
            raise ValueError(
                f"unknown daemon kind {self.kind!r}; "
                f"one of {DAEMON_KINDS}")

    def to_dict(self) -> dict:
        return {"kind": self.kind, "ident": self.ident,
                "monmap": self.monmap, "wal_path": self.wal_path,
                "osd_config": dict(self.osd_config),
                "fault_seed": self.fault_seed,
                "asok_path": self.asok_path,
                "ready_path": self.ready_path,
                "extra": dict(self.extra)}

    @classmethod
    def from_dict(cls, d: dict) -> "DaemonSpec":
        return cls(kind=d["kind"], ident=str(d["ident"]),
                   monmap=d.get("monmap"), wal_path=d.get("wal_path"),
                   osd_config=dict(d.get("osd_config") or {}),
                   fault_seed=d.get("fault_seed"),
                   asok_path=d.get("asok_path"),
                   ready_path=d.get("ready_path"),
                   extra=dict(d.get("extra") or {}))

    @property
    def name(self) -> str:
        return f"{self.kind}.{self.ident}"


class ProcHandle:
    """Parent-side handle on one spawned daemon process."""

    def __init__(self, spec: DaemonSpec, proc: subprocess.Popen,
                 log_path: str):
        self.spec = spec
        self.proc = proc
        self.log_path = log_path

    @property
    def pid(self) -> int:
        return self.proc.pid

    def alive(self) -> bool:
        return self.proc.poll() is None

    def signal(self, sig: int) -> None:
        os.kill(self.pid, sig)

    def kill9(self) -> None:
        """True process death: SIGKILL, then reap the zombie."""
        try:
            self.proc.kill()
        except OSError:
            pass
        self.wait(timeout=10)

    def terminate(self) -> None:
        try:
            self.proc.terminate()
        except OSError:
            pass

    def wait(self, timeout: float | None = None) -> int | None:
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            return None
        unregister_pid(self.pid)
        return rc

    def stop(self, timeout: float = 10.0) -> int | None:
        """Clean shutdown: SIGTERM, escalate to SIGKILL at timeout."""
        if not self.alive():
            return self.wait(timeout=timeout)
        self.terminate()
        rc = self.wait(timeout=timeout)
        if rc is None:
            self.kill9()
            rc = self.proc.returncode
        return rc

    def log_tail(self, n: int = 40) -> str:
        try:
            with open(self.log_path, "r", errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return "<no log>"

    def __repr__(self):
        state = "alive" if self.alive() else \
            f"exit={self.proc.returncode}"
        return f"ProcHandle({self.spec.name}, pid={self.pid}, {state})"


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spawn_daemon(spec: DaemonSpec, *, retries: int = 2,
                 timeout: float = 30.0,
                 run_dir: str | None = None) -> ProcHandle:
    """Spawn one daemon process from its boot spec and wait for the
    readiness file.  A failed attempt (exit before ready, or deadline)
    is killed, reaped, and retried; exhaustion raises
    :class:`ProcSpawnError` carrying the last log tail."""
    if run_dir is None:
        run_dir = tempfile.mkdtemp(prefix="ceph-tpu-procs-")
    if spec.ready_path is None:
        spec.ready_path = os.path.join(
            run_dir, f"{spec.name}.ready")
    spec_path = os.path.join(run_dir, f"{spec.name}.spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec.to_dict(), f)
    log_path = os.path.join(run_dir, f"{spec.name}.log")
    env = dict(os.environ)
    env[PROC_ENV] = "1"
    # one process per chip: the parent may hold it, so the daemon's
    # lanes run on the CPU
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _repo_root() + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    last_err = "never attempted"
    attempts = 1 + max(0, int(retries))
    for attempt in range(attempts):
        try:
            os.unlink(spec.ready_path)
        except FileNotFoundError:
            pass
        with open(log_path, "ab") as logf:
            logf.write(
                f"--- spawn attempt {attempt + 1}/{attempts} "
                f"{spec.name} ---\n".encode())
            logf.flush()
            proc = subprocess.Popen(
                [sys.executable, "-m", "ceph_tpu.procs", spec_path],
                stdout=logf, stderr=subprocess.STDOUT,
                stdin=subprocess.DEVNULL, env=env,
                start_new_session=True)
        handle = ProcHandle(spec, proc, log_path)
        register_pid(handle)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if os.path.exists(spec.ready_path):
                try:
                    with open(spec.ready_path) as f:
                        ready = json.load(f)
                except (OSError, ValueError):
                    time.sleep(0.01)    # racing the atomic rename
                    continue
                if int(ready.get("pid", -1)) == proc.pid:
                    return handle
                # stale ready file from a previous incarnation on the
                # same path: ignore it and keep waiting for ours
            if proc.poll() is not None:
                last_err = (f"exited rc={proc.returncode} before "
                            f"ready: {handle.log_tail()}")
                break
            time.sleep(0.02)
        else:
            last_err = f"not ready in {timeout}s: {handle.log_tail()}"
        handle.kill9()
    raise ProcSpawnError(
        f"{spec.name}: spawn failed after {attempts} attempt(s): "
        f"{last_err}")


def write_ready(spec: DaemonSpec) -> None:
    """Atomic readiness handshake (child side): tmp + rename so the
    parent never reads a torn file.  The wall/mono clock pair is this
    process's monotonic-to-wall alignment — the parent rebases the
    child's span starts and black-box stamps with it when merging
    cross-process timelines (asok dump headers carry the same pair,
    fresher; the readiness file is the fallback that survives the
    daemon's death)."""
    if not spec.ready_path:
        return
    tmp = spec.ready_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"pid": os.getpid(), "ident": spec.ident,
                   "kind": spec.kind, "wall": time.time(),
                   "mono": time.monotonic()}, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, spec.ready_path)


# -- child entrypoint -----------------------------------------------------
def _seed_faults(msgr, fault_seed) -> None:
    if fault_seed is None:
        return
    import random as _random
    msgr.faults.seed = int(fault_seed)
    msgr.faults.rng = _random.Random(int(fault_seed))


def _build_mon(spec: DaemonSpec):
    from .mon.monitor import MonMap, Monitor
    mon = Monitor(int(spec.ident), MonMap.from_dict(spec.monmap),
                  admin_socket_path=spec.asok_path)
    _seed_faults(mon.msgr, spec.fault_seed)
    mon.start()
    return mon


def _build_osd(spec: DaemonSpec):
    from .mon.monitor import MonMap
    from .os_store import CrashInjector, WALStore
    from .osd.daemon import OSDaemon

    whoami = int(spec.ident)
    cfg = None
    if spec.osd_config:
        from .core.config import ConfigProxy
        from .core.options import build_options
        cfg = ConfigProxy(build_options())
        for k, v in spec.osd_config.items():
            cfg.set(k, v)
    store = None
    if spec.wal_path and spec.osd_config.get(
            "osd_objectstore", "walstore") == "walstore":
        inj = CrashInjector(seed=int(spec.fault_seed or 0),
                            osd=f"osd.{whoami}")
        for point, prob in (spec.extra.get("crash_probs")
                            or {}).items():
            inj.set_prob(point, float(prob))
        store = WALStore(
            spec.wal_path,
            sync_mode=spec.osd_config.get("osd_wal_sync_mode",
                                          "batch"),
            name=f"osd.{whoami}", crash=inj,
            compact_min_records=int(spec.osd_config.get(
                "osd_wal_compact_min_records", 0)))
    osd = OSDaemon(whoami, MonMap.from_dict(spec.monmap),
                   store=store, config=cfg,
                   admin_socket_path=spec.asok_path)
    _seed_faults(osd.msgr, spec.fault_seed)
    osd.start(wait_for_up=True,
              timeout=float(spec.extra.get("boot_timeout", 30.0)))
    return osd


def _build_mgr(spec: DaemonSpec):
    import importlib

    from .mgr.daemon import MgrDaemon
    from .mon.monitor import MonMap
    modules = None
    if spec.extra.get("modules"):
        # dotted "pkg.mod:Class" strings — classes don't serialize
        modules = []
        for path in spec.extra["modules"]:
            modname, _, clsname = path.partition(":")
            modules.append(
                getattr(importlib.import_module(modname), clsname))
    mgr = MgrDaemon(spec.ident, MonMap.from_dict(spec.monmap),
                    modules=tuple(modules) if modules else None,
                    asok_paths=spec.extra.get("asok_paths"),
                    admin_socket_path=spec.asok_path)
    _seed_faults(mgr.msgr, spec.fault_seed)
    mgr.start()
    return mgr


def _build_msgr_victim(spec: DaemonSpec):
    """Accept-side messenger that records every MGenericReply.result
    (one int per line, flushed) to extra["out_path"] — the kill-the-
    accepting-end-mid-stream target for tests/test_msgr.py.  Stays
    jax-free: the msg import chain never touches numpy or jax."""
    from .msg import Dispatcher, MGenericReply, Messenger

    out = open(spec.extra["out_path"], "a", buffering=1)

    class _Sink(Dispatcher):
        def ms_dispatch(self, msg):
            if isinstance(msg, MGenericReply):
                out.write(f"{msg.result}\n")
                return True
            return False

    msgr = Messenger(spec.extra.get("entity", "osd.victim"))
    msgr.add_dispatcher(_Sink())
    msgr.bind("127.0.0.1", int(spec.extra["port"]))
    return msgr


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python -m ceph_tpu.procs <spec.json>",
              file=sys.stderr)
        return 2
    with open(argv[0]) as f:
        spec = DaemonSpec.from_dict(json.load(f))
    stop = threading.Event()

    def _on_sigterm(signum, frame):   # noqa: ARG001
        stop.set()

    signal.signal(signal.SIGTERM, _on_sigterm)
    signal.signal(signal.SIGINT, _on_sigterm)
    builders = {"mon": _build_mon, "osd": _build_osd,
                "mgr": _build_mgr, "msgr_victim": _build_msgr_victim}
    daemon = builders[spec.kind](spec)
    write_ready(spec)
    stop.wait()
    try:
        daemon.shutdown()
    except Exception:   # noqa: BLE001 — exiting anyway
        pass
    # skip interpreter teardown: daemon threads mid-poll segfault-free
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())

"""Where an op's time goes inside the OSD and the messenger: Ceph's op
events on the OSD's TrackedOp, the stage latency counters computed from
them, the messenger's frame and byte counters, objecter resends as a
count, and the host stages a profiler trace names."""

import glob
import os
import subprocess
import sys
import threading
import time

import pytest

from ceph_tpu.core.admin_socket import admin_command
from ceph_tpu.core.config import ConfigProxy
from ceph_tpu.core.options import build_options
from ceph_tpu.msg import Dispatcher, Messenger
from ceph_tpu.msg.message import MGenericPing, MGenericReply
from ceph_tpu.osd import messages as M
from ceph_tpu.osd.daemon import _build_osd_perf
from ceph_tpu.vstart import MiniCluster

EC_WRITE_EVENTS = ["initiated", "queued_for_pg", "reached_pg", "started",
                   "encode_submitted", "encoded", "sub_op_sent",
                   "sub_op_commit_rec", "commit_sent", "done"]
HOST_STAGES = {"msgr_send", "msgr_recv", "osd_op", "engine_launch",
               "wal_commit"}


@pytest.fixture(scope="module")
def cluster():
    c = MiniCluster(n_mons=1, n_osds=3)
    c.start()
    r = c.rados()
    rc, outs, _ = r.mon_command({
        "prefix": "osd pool create", "pool": "ec", "pg_num": 4,
        "size": 3, "pool_type": "erasure"})
    assert rc == 0, outs
    c.wait_for_clean()
    r.open_ioctx("ec").write_full("warm", b"w" * 8192)   # compiles
    yield c, r
    c.stop()


def _perf(c) -> dict[int, dict]:
    return {i: admin_command(o.admin_socket.path, "perf dump")[f"osd.{i}"]
            for i, o in c.osds.items()}


def _historic(c, oid: str) -> tuple[int, dict]:
    """(primary, its historic op) of the latest op on ``oid``."""
    for i, o in c.osds.items():
        ops = admin_command(o.admin_socket.path, "dump_historic_ops")["ops"]
        mine = [op for op in ops if f" {oid} " in op["description"]]
        if mine:
            return i, mine[-1]
    raise AssertionError(f"no historic op for {oid}")


def _delta(after: dict, before: dict, name: str) -> tuple[float, int]:
    return (after[name]["sum"] - before[name]["sum"],
            after[name]["avgcount"] - before[name]["avgcount"])


class TestOpEvents:
    def test_ec_write_marks_ceph_events_in_order(self, cluster):
        c, r = cluster
        r.open_ioctx("ec").write_full("ev-obj", b"e" * 8192)
        _primary, op = _historic(c, "ev-obj")
        names = [e["event"] for e in op["events"]]
        assert names == EC_WRITE_EVENTS
        times = [e["time"] for e in op["events"]]
        assert times == sorted(times)

    def test_op_latency_runs_from_reached_pg(self, cluster):
        c, r = cluster
        before = _perf(c)
        r.open_ioctx("ec").write_full("lat-obj", b"l" * 8192)
        primary, op = _historic(c, "lat-obj")
        after = _perf(c)[primary]
        b = before[primary]
        at = {e["event"]: e["time"] for e in op["events"]}
        lat, n = _delta(after, b, "op_latency")
        wait, n_wait = _delta(after, b, "op_before_dequeue_op_lat")
        assert n == n_wait == 1
        # dumps round to the microsecond
        assert lat == pytest.approx(op["age"] - at["reached_pg"],
                                    abs=5e-6)
        assert wait == pytest.approx(at["reached_pg"], abs=5e-6)
        assert wait + lat <= op["age"] + 5e-6
        enc, n_enc = _delta(after, b, "op_encode_lat")
        sub, n_sub = _delta(after, b, "op_subop_commit_lat")
        assert n_enc == n_sub == 1
        assert enc == pytest.approx(
            at["encoded"] - at["encode_submitted"], abs=5e-6)
        assert sub == pytest.approx(
            at["sub_op_commit_rec"] - at["sub_op_sent"], abs=5e-6)

    def test_resend_counts_and_carries_retry_attempt(self, cluster):
        c, _r = cluster
        cfg = ConfigProxy(build_options())
        cfg.set("objecter_resend_interval", 0.3)
        cfg.set("objecter_resend_jitter", 0.0)
        r = c.rados(config=cfg)
        try:
            io = r.open_ioctx("ec")
            io.write_full("pre", b"warm the connections")
            before = sum(d["op_resent"] for d in _perf(c).values())
            r.objecter.msgr.faults.set_rule("*", "*", drop=1.0)
            t = threading.Timer(0.7, r.objecter.msgr.faults.heal)
            t.start()
            io.write_full("resent-obj", b"survives the drop")
            t.join()
            after = sum(d["op_resent"] for d in _perf(c).values())
            assert after > before
        finally:
            r.shutdown()

    def test_retry_attempt_on_the_wire(self):
        msg = M.MOSDOp(tid=1, client="c", pgid="1.0", oid="o", epoch=1,
                       ops=[], flags=0, retry_attempt=2)
        assert M.MOSDOp.decode(msg.encode()).retry_attempt == 2
        # an older sender's MOSDOp has no such field
        old = M.MOSDOp(tid=1, client="c", pgid="1.0", oid="o", epoch=1,
                       ops=[], flags=0)
        old.FIELDS = tuple(f for f in M.MOSDOp.FIELDS
                           if f != "retry_attempt")
        assert M.MOSDOp.decode(old.encode()).retry_attempt is None


class _Sink(Dispatcher):
    def __init__(self):
        self.got = []

    def ms_dispatch(self, msg) -> bool:
        self.got.append(msg)
        return True


class TestMessengerCounters:
    def test_frame_counts_and_payload_bytes(self):
        a, b = Messenger("client.a"), Messenger("osd.b")
        a.perf, b.perf = _build_osd_perf("a"), _build_osd_perf("b")
        sink = _Sink()
        b.add_dispatcher(sink)
        try:
            con = a.connect_to(b.bind())
            msgs = [MGenericPing(stamp=float(i)) for i in range(5)] + \
                [MGenericReply(what="x" * (100 * i), result=i)
                 for i in range(5)]
            payload = sum(len(m.encode()) for m in msgs)
            for m in msgs:
                con.send_message(m)
            deadline = time.monotonic() + 10
            while (b.perf.get("msgr_recv_messages") < len(msgs)
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert len(sink.got) == len(msgs)
            for side, kind in ((a, "send"), (b, "recv")):
                assert side.perf.get(f"msgr_{kind}_messages") == len(msgs)
                assert side.perf.get(f"msgr_{kind}_bytes") == payload
                d = side.perf.dump()[side.perf.name]
                t = d[f"msgr_running_{kind}_time"]
                assert t["avgcount"] == len(msgs) and t["sum"] > 0
            # nothing flowed the other way but acks, which are no
            # messages
            assert a.perf.get("msgr_recv_messages") == 0
            assert b.perf.get("msgr_send_messages") == 0
        finally:
            a.shutdown()
            b.shutdown()


class TestProfilerStages:
    def test_ec_write_trace_names_host_stages(self, cluster, tmp_path):
        import jax
        c, r = cluster
        io = r.open_ioctx("ec")
        jax.profiler.start_trace(str(tmp_path))
        try:
            io.write_full("traced-obj", b"t" * 8192)
            time.sleep(0.2)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(str(tmp_path), "**",
                                         "*.xplane.pb"), recursive=True)
        pd = jax.profiler.ProfileData.from_file(path)
        names = {ev.name for pl in pd.planes for ln in pl.lines
                 for ev in ln.events}
        assert HOST_STAGES <= names

    def test_stage_without_session_does_not_import_jax(self):
        code = ("import sys\n"
                "from ceph_tpu.core.tracer import stage\n"
                "with stage('osd_op'):\n"
                "    pass\n"
                "assert 'jax' not in sys.modules, 'jax imported'\n")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = subprocess.run([sys.executable, "-c", code], cwd=root,
                             capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr


class TestHeartbeatRace:
    def test_peer_entry_popped_mid_heartbeat_keeps_the_tick(self, cluster):
        """A map advance pops a peer's heartbeat stamp while the tick
        pings it: the tick must survive, or the OSD never retries
        peering again."""
        c, _r = cluster
        osd = c.osds[0]
        send = osd.send_to_osd

        def send_then_pop(o, msg):
            send(o, msg)
            osd._hb_last.pop(o, None)
        osd.send_to_osd = send_then_pop
        try:
            osd._heartbeat()
        finally:
            del osd.send_to_osd

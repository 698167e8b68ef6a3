"""OSD messenger time per client op (ms/op): the window delta of every
OSD's ``msgr_running_send_time`` (encode, frame, socket write) plus
``msgr_running_recv_time`` (decode, dispatch), over the client ops
completed in the window.  The client's own messenger is not in it."""

from benchmark.osd_counters import client_ok, delta


def read(run):
    send = delta(run, "msgr_running_send_time")
    recv = delta(run, "msgr_running_recv_time")
    ops = len(client_ok(run))
    if send is None or recv is None or not ops:
        return None
    return 1000.0 * (send[0] + recv[0]) / ops

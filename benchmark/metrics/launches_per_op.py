"""Device launches of every batch-engine lane (write, reconstruct,
compression) in the window, per client op completed in it, from each
OSD's ``dump_batch_engine``."""

LANES = ("launches", "recon_launches", "comp_launches")


def read(run):
    ops = sum(1 for r in run.records
              if r.status == "ok" and r.t_done <= run.t_end)
    launches = sum(a.get(k, 0) - b.get(k, 0)
                   for b, a in zip(run.before["engine"],
                                   run.after["engine"])
                   for k in LANES)
    return launches / ops if ops else None

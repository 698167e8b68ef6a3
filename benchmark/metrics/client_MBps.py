"""Client MiB/s: logical bytes of acknowledged writes and verified
reads that completed inside the window, over the window's length
(``rados bench`` / ``rbd bench`` "Bandwidth (MB/sec)", which counts
MiB)."""


def read(run):
    done = sum(r.nbytes for r in run.records
               if r.status == "ok" and r.t_done <= run.t_end)
    return done / 2**20 / (run.t_end - run.t0)

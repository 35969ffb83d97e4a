"""job_s: seconds to finish one job, on the host clock.

(end of the window's last job - window start) / jobs finished: all the
time of the window over all its jobs, the gaps between jobs included.
"""


def read(record, cell):
    return record.window_s / record.jobs if record.jobs else None

"""Mean seconds per job of the window spent in the operand upload: building
the superstep and transferring the packed operands and the first state
(span `nai.offline.upload`), `upload_s` of `run_full_graph_infer`'s
`"offline.job"` records."""
from yardstick.spans import mean, offline_jobs


def read(rec):
    return mean(offline_jobs(rec), "upload_s")

"""Mean seconds per job of the window spent in writing the result (span
`nai.offline.result`), `result_s` of `run_full_graph_infer`'s
`"offline.job"` records."""
from yardstick.spans import mean, offline_jobs


def read(rec):
    return mean(offline_jobs(rec), "result_s")

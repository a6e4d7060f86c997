"""Mean seconds per job of the window spent in fetching the state to the host
after each superstep (span `nai.offline.fetch`), `fetch_s` of
`run_full_graph_infer`'s `"offline.job"` records."""
from yardstick.spans import mean, offline_jobs


def read(rec):
    return mean(offline_jobs(rec), "fetch_s")

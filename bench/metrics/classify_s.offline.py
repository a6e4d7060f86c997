"""Mean seconds per job of the window spent in classification: stacking the
series and the chunked per-order classifier (span `nai.offline.classify`),
`classify_s` of `run_full_graph_infer`'s `"offline.job"` records."""
from yardstick.spans import mean, offline_jobs


def read(rec):
    return mean(offline_jobs(rec), "classify_s")

"""gateway: the clients' median time to first token less the scheduler's own
(``sched.stats()["ttft_p50_s"]``, cumulative since load), same host clock."""

from perfbench import stats


def read(layer):
    if layer.get("kind") != "serve" or "ttft_p50_s" not in layer["after"]:
        return None
    ttft = layer["numbers"]["ttft_ms"]
    if not ttft:
        return None
    return stats.percentile(ttft, 50) - 1e3 * layer["after"]["ttft_p50_s"]

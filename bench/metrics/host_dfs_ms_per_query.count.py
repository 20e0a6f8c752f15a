"""host_dfs_ms_per_query.count: host time in the `cemr.host_dfs` spans
(`MatchStats.span_host_dfs_s`: the queries engine `auto` sends to the host
DFS), summed over the window's requests, in ms per completed request."""


def read(run):
    s = run.counters.get("span_host_dfs_s")
    return 1e3 * s / run.completed if s is not None and run.completed \
        else None

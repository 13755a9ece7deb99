"""Median time of the analyst's selects as AnalysisSession reports them
(QueryResult.elapsed_s), without the wait before a late query starts."""
from harness import quantile


def read(run):
    q = run["record"].get("query_elapsed_s")
    return quantile(q, 0.5) * 1e3 if q else None

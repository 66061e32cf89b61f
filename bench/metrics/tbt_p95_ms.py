"""tbt_p95_ms: 95th percentile of the gap between consecutive tokens of one
request, over every such gap in the window."""
from bench import stats


def read(run):
  p = stats.percentile(stats.token_gaps(run), 95)
  return None if p is None else 1e3 * p

"""pq_decode_roofline: the paged PQ decode kernel's share of its roofline,
in % (`costs/pq_decode_attention_paged.py`, `stats.roofline_share`)."""
from bench import stats
from bench.costs import pq_decode_attention_paged


def read(run):
  return stats.roofline_share(run, pq_decode_attention_paged)

"""paged_flash_roofline: the paged exact decode kernel's share of its
roofline, in % (`costs/paged_flash_decode.py`, `stats.roofline_share`)."""
from bench import stats
from bench.costs import paged_flash_decode


def read(run):
  return stats.roofline_share(run, paged_flash_decode)

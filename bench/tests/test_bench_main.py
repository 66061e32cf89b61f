"""`run.py` and `control.py` refuse a device that is not a TPU: a non-zero
exit and no result line."""
import io
import pathlib
import sys
from contextlib import redirect_stderr, redirect_stdout

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import control, run  # noqa: E402


@pytest.mark.parametrize("main,argv", [
    (run.main, ["--workload", "yi-34b-exact.ctx4k-decode", "--seed",
                "4294967301", "--seconds", "1", "--trace", "0"]),
    (control.main, ["--workload", "yi-34b-exact.ctx4k-decode", "--seeds",
                    "1", "--seconds", "1"]),
])
def test_refuses_a_device_that_is_not_a_tpu(main, argv):
  assert jax.devices()[0].platform != "tpu"
  out, err = io.StringIO(), io.StringIO()
  with redirect_stdout(out), redirect_stderr(err):
    rc = main(argv)
  assert rc != 0
  assert out.getvalue() == ""
  assert err.getvalue()

"""Host seconds a step that go into getting an epoch's batches onto the
device, before the epoch program is dispatched: the spans ``load_batches``
(the loader's gather), ``stack_batches`` (``np.stack``) and ``h2d``
(``jnp.asarray`` / ``device_put`` returning: the copy itself goes on under
``wait_device``) over the steps dispatched.  Median over the window's epochs
under the profiler (`chipbench/spans.py:window_periods` says why those)."""

from chipbench.spans import STAGING, median_over_window, per_step_ms


def read(run):
    return median_over_window(run, lambda r: per_step_ms(r, STAGING),
                              under_profiler=True)

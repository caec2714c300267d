"""Pipeline: the share of the hetero engine's step time that its S loop
spent waiting for R-worker results (window deltas of ``hotpath_stats``
``r_wait_s`` over ``step_s``), in percent."""


def read(run):
    step = run.hot1.get("step_s", 0.0) - run.hot0.get("step_s", 0.0)
    if step <= 0:
        return None
    wait = run.hot1.get("r_wait_s", 0.0) - run.hot0.get("r_wait_s", 0.0)
    return 100.0 * wait / step

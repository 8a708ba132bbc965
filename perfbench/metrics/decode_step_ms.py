"""Engine decode (``ContinuousSession.run_segment``): time in decode
segments over the decode-loop steps they ran, all nodes together.
Source: the harness's clock around ``run_segment`` and the session's
step counter."""


def read(run):
    probe = run["probe"]
    if not run.get("same_sessions", False):
        return None
    steps = sum(probe.seg_steps.values())
    if not steps:
        return None
    return 1e3 * sum(probe.seg_time.values()) / steps

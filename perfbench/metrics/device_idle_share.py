"""Device: share of the traced window in which no operation ran on the
chip (1 - union of device-op intervals / window), in %.  The window is
the part of the measured window that the device trace covers."""


def read(run):
    win = run.get("device_window")
    if win is None or "busy_s" not in run:
        return None
    span = (win[1] - win[0]) / 1e9
    return 100.0 * (1.0 - run["busy_s"] / span) if span > 0 else None

"""Engine prefill (``serving/engine.py`` refill and prefix prefill):
tokens staged per model pass in the window, pads included — the
``staged_tokens`` over the ``passes`` of the program's refill
``prefill`` spans (one per admission, its prefix prefill included).  A
program whose spans carry no ``passes`` reads as nothing."""


def read(run):
    passes = tokens = 0
    for e in run.get("spans", []):
        a = e.get("attrs") or {}
        if e.get("name") == "prefill" and "passes" in a:
            passes += a["passes"]
            tokens += a["staged_tokens"]
    return tokens / passes if passes else None

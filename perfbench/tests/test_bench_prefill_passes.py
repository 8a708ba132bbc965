"""``prefill_tokens_per_pass``: nothing on a program whose spans carry
no pass counts, the staged tokens over the passes where they do."""
from perfbench import spec


def _span(name, **attrs):
    return {"kind": "span", "name": name, "trace": "r1", "t0": 0.0,
            "t1": 0.1, "attrs": attrs}


def test_reads_nothing_without_pass_counts():
    read = spec.load_reader("prefill_tokens_per_pass")
    assert read({}) is None
    assert read({"spans": [_span("prefill", mode="refill", slot=0),
                           _span("prefix_prefill", tokens=1050)]}) is None


def test_reads_staged_tokens_over_passes():
    read = spec.load_reader("prefill_tokens_per_pass")
    spans = [
        # a prefix miss: 33-chunk prefix in 2 passes + 1-chunk suffix
        _span("prefill", mode="refill", passes=3, staged_tokens=1088),
        _span("prefix_prefill", tokens=1050, passes=2),   # nested: not
        _span("prefill", mode="refill", passes=1, staged_tokens=32),
        _span("prefill", mode="frame", rows=1),
        _span("decode_segment", passes=9, staged_tokens=9)]
    assert read({"spans": spans}) == (1088 + 32) / 4

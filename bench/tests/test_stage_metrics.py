"""The readers of the program's stage spans (``ingest_s``, ``publish_s``,
``eval_s``, ``crypto_s``) on a synthetic window (CPU; no program runs)."""
import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fedbench import spec  # noqa: E402

READS = {"ingest_s": ("server.ingest",),
         "publish_s": ("server.publish_global",),
         "eval_s": ("client.eval",),
         "crypto_s": ("wire.encrypt", "wire.decrypt")}


def span(name, t0, t1, actor="server"):
    return SimpleNamespace(name=name, actor=actor, t0=t0, t1=t1, attrs={})


def window(spans, n_updates=2):
    return SimpleNamespace(lo=10.0, hi=20.0, window_s=10.0,
                           n_updates=n_updates, spans=spans)


@pytest.mark.parametrize("metric", sorted(READS))
def test_nothing_to_read_is_none(metric):
    others = [span("client.post", 11.0, 12.0), span("sched.tick", 10.0, 20.0)]
    assert spec.reader(metric)(window(others)) is None


@pytest.mark.parametrize("metric", sorted(READS))
def test_spans_inside_the_window_over_updates(metric):
    name = READS[metric][0]
    spans = [span(name, 11.0, 12.5), span(name, 14.0, 15.0),
             span(name, 9.0, 10.5),            # opens before the window
             span(name, 19.5, 21.0),           # closes after it
             span("client.post", 12.0, 13.0)]
    assert spec.reader(metric)(window(spans, 2)) == pytest.approx(
        (1.5 + 1.0) / 2)
    assert spec.reader(metric)(window(spans, 5)) == pytest.approx(2.5 / 5)


def test_crypto_sums_both_directions_over_every_actor():
    spans = [span("wire.encrypt", 11.0, 12.0, "server"),
             span("wire.decrypt", 12.0, 14.0, "client-a"),
             span("wire.encrypt", 15.0, 15.5, "client-b"),
             span("wire.decrypt", 16.0, 16.25, "server"),
             span("wire.pack", 13.0, 14.0, "server"),
             span("wire.unpack", 17.0, 18.0, "client-a"),
             span("wire.decrypt", 19.0, 20.5, "client-a")]
    assert spec.reader("crypto_s")(window(spans, 1)) == pytest.approx(
        1.0 + 2.0 + 0.5 + 0.25)

"""The comparison that decides ``correct`` still sees the scoring kernel's
outputs that a dispatch leaves on the device: a fault in one of them alone
comes out not correct."""

from collections.abc import Mapping

from test_correct import _own_compile_cache, _wrap_design_kernel, checks, run

__all__ = ["_own_compile_cache"]  # the autouse fixture, for these tests too


class _MacsOff(Mapping):
    """The kernel's outputs with ``macs`` 1e-6 off, read as lazily as the
    outputs themselves: nothing is copied that the caller does not read."""

    def __init__(self, out):
        self._out = out

    def __getitem__(self, key):
        v = self._out[key]
        return v * (1 + 1e-6) if key == "macs" else v

    def __iter__(self):
        return iter(self._out)

    def __len__(self):
        return len(self._out)


def test_deferred_output_altered_fails(monkeypatch):
    _wrap_design_kernel(monkeypatch, _MacsOff)
    out = run()
    assert not out["correct"]
    c = checks(out)
    assert c["score_gap"] > out["checks"]["score_gap"]["limit"]
    assert c["answer_gap"] == 0.0  # the choice reads only the two scores

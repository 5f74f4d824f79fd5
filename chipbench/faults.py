"""The control and the planted faults that ``correct`` has to catch.

Each wraps (or stands in for) the system under test, underneath the
harness, so a run drives the same requests and the same comparison:

  * ``control``: the plain reference put in the program's place, holding
    its values in float32, the nearest narrower type a later change could
    be tempted to store them in.  It breaks the configurations' stated
    guarantee of exact answers.
  * ``state_unchanged``: every update after the load is acknowledged and
    not applied (a step that returns its state unchanged).
  * ``half_batch``: a lookup answers the first half of its keys and
    leaves out the rest.
  * ``altered_answer``: one answer of every lookup is altered where it
    is produced.

There is one chip per cell and no exchange between chips to leave out.
"""
from __future__ import annotations

import numpy as np

MISS = np.uint32(0xFFFFFFFF)
FAULTS = ("control", "state_unchanged", "half_batch", "altered_answer")


class Control:
    """The reference in the program's place, with float32 values."""

    def __init__(self, cfg: dict, reference):
        self.cfg, self.reference, self.map = cfg, reference, None

    def insert(self, keys, values):
        f32 = np.asarray(values, np.uint32).astype(np.float32)
        narrowed = np.minimum(f32.astype(np.float64),
                              float(MISS) - 1).astype(np.uint32)
        if self.map is None:
            self.map = self.reference.KVMap(keys, narrowed)
        else:
            self.map.update(keys, narrowed)

    def lookup(self, keys):
        return self.map.get(keys)

    def wait_in_sync(self, timeout):
        return True

    def force_route(self, route):
        pass

    def warm_replay_shapes(self):
        pass

    def counters(self):
        return {"routed_shortcut": 0, "routed_traditional": 0,
                "replay_seconds": 0.0, "populate_seconds": 0.0}

    def entries(self):
        return int(self.map.keys.size)

    def dropped(self):
        return 0

    def layout(self):
        return None

    def close(self):
        pass


class _Wrapped:
    def __init__(self, inner):
        self.inner = inner
        self.loaded = False

    def __getattr__(self, name):
        return getattr(self.inner, name)


class StateUnchanged(_Wrapped):
    def insert(self, keys, values):
        if not self.loaded:
            self.inner.insert(keys, values)
            self.loaded = True


class HalfBatch(_Wrapped):
    def lookup(self, keys):
        keys = np.asarray(keys, np.uint32)
        half = keys.size // 2
        out = np.full(keys.size, MISS)
        out[:half] = np.asarray(self.inner.lookup(keys[:half]))
        return out


class AlteredAnswer(_Wrapped):
    def lookup(self, keys):
        out = np.array(self.inner.lookup(keys))
        out[0] ^= np.uint32(1)
        return out


def build(fault: str, cfg: dict, make_system, reference):
    if fault == "control":
        return Control(cfg, reference)
    wrap = {"state_unchanged": StateUnchanged, "half_batch": HalfBatch,
            "altered_answer": AlteredAnswer}[fault]
    return wrap(make_system(cfg))

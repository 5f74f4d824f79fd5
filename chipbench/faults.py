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
  * ``low_word_only``: the plain reference in the program's place, keyed
    on each key's low 32 bits, as an index that wraps 64-bit keys into
    32-bit words would be.  Stored YCSB keys never share a low word, so
    only the harness's miss probe of never-stored keys sees it; a
    configuration of 32-bit keys refuses it, having nothing to catch.

There is one chip per cell and no exchange between chips to leave out.
"""
from __future__ import annotations

import numpy as np

from chipbench import gen

FAULTS = ("control", "state_unchanged", "half_batch", "altered_answer",
          "low_word_only")


class StandIn:
    """The plain reference in the program's place, behind the adapter's
    interface, with every key and value exact."""

    def __init__(self, cfg: dict, reference):
        self.cfg, self.reference, self.map = cfg, reference, None

    def stored(self, keys, values):
        """The keys and values the map holds for an acknowledged write."""
        return keys, values

    def asked(self, keys):
        """The keys the map is asked for on a read."""
        return keys

    def insert(self, keys, values):
        keys, values = self.stored(keys, values)
        if self.map is None:
            self.map = self.reference.KVMap(keys, values)
        else:
            self.map.update(keys, values)

    def lookup(self, keys):
        return self.map.get(self.asked(keys))

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


class Control(StandIn):
    """The reference in the program's place, with float32 values."""

    def stored(self, keys, values):
        values = np.asarray(values)
        f32 = values.astype(np.float32).astype(np.float64)
        below_miss = np.nextafter(float(np.iinfo(values.dtype).max), 0.0)
        return keys, np.minimum(f32, below_miss).astype(values.dtype)


class LowWordOnly(StandIn):
    """The reference in the program's place, keyed on the low 32 bits."""

    def asked(self, keys):
        return np.asarray(keys) & np.uint64(0xFFFFFFFF)

    def stored(self, keys, values):
        return self.asked(keys), values


class _Wrapped:
    def __init__(self, inner, cfg: dict):
        self.inner, self.cfg = inner, cfg
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
        keys = np.asarray(keys)
        half = keys.size // 2
        out = np.full(keys.size, gen.miss(self.cfg.get("value_bits", 32)))
        out[:half] = np.asarray(self.inner.lookup(keys[:half]))
        return out


class AlteredAnswer(_Wrapped):
    def lookup(self, keys):
        out = np.array(self.inner.lookup(keys))
        out[0] ^= out.dtype.type(1)
        return out


def build(fault: str, cfg: dict, make_system, reference):
    if fault == "control":
        return Control(cfg, reference)
    if fault == "low_word_only":
        if int(cfg.get("key_bits", 32)) == 32:
            raise ValueError("low_word_only keeps the low 32 bits of a "
                             "key: at 32-bit keys it has nothing to catch")
        return LowWordOnly(cfg, reference)
    wrap = {"state_unchanged": StateUnchanged, "half_batch": HalfBatch,
            "altered_answer": AlteredAnswer}[fault]
    return wrap(make_system(cfg), cfg)

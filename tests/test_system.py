"""End-to-end behaviour tests for the paper's system: the Fig-8-style
mixed workload (insert bursts -> shortcut goes out of sync -> catches up)
and a crash/restore training loop over the real substrate."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sharded_eh import ShardedShortcutEH

from conftest import unique_keys


def test_mixed_workload_sync_cycle(rng):
    """Paper Fig. 8: bulk-load, then waves of 1% inserts + 99% lookups.
    After each insert burst the shortcut is stale (lookups still correct
    via the traditional path); after maintenance it serves again."""
    keys = unique_keys(rng, 3000)
    sc = ShardedShortcutEH(10, 16, 2048, num_shards=1)
    sc.insert(keys[:2400], np.arange(2400, dtype=np.uint32))  # bulk load
    sc.pump()
    assert sc.in_sync() and sc.avg_fan_in() <= 8.0

    inserted = 2400
    for wave in range(4):
        burst = keys[inserted:inserted + 150]
        sc.insert(burst, np.arange(inserted, inserted + 150,
                                   dtype=np.uint32))
        inserted += 150
        assert not sc.in_sync()            # stale immediately after burst
        lookups = keys[:inserted]
        tr0 = sc.routed_traditional
        out = np.asarray(sc.lookup_batched(lookups))
        assert sc.routed_traditional == tr0 + 1   # routed traditional
        np.testing.assert_array_equal(out, np.arange(inserted,
                                                     dtype=np.uint32))
        sc.pump()                          # mapper catches up
        assert sc.in_sync()
        sc0 = sc.routed_shortcut
        out = np.asarray(sc.lookup_batched(lookups))
        assert sc.routed_shortcut == sc0 + 1      # routed shortcut again
        np.testing.assert_array_equal(out, np.arange(inserted,
                                                     dtype=np.uint32))
    assert sc.routed_shortcut == 4
    assert sc.routed_traditional == 4


def test_train_crash_restore_bitwise(tmp_path):
    """Kill the training loop mid-run, restore from checkpoint, and land
    on bitwise-identical parameters vs an uninterrupted run (deterministic
    data pipeline + atomic checkpoints)."""
    from repro.checkpoint.checkpointer import Checkpointer, latest_step
    from repro.configs import get
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.models import model as M
    from repro.optim.adamw import adamw_init
    from repro.optim.schedule import wsd_schedule
    from repro.runtime.train import make_train_step

    cfg = get("internlm2_1_8b").reduced()
    pipe = SyntheticLM(cfg, DataConfig(seq_len=32, global_batch=4))
    step_fn = jax.jit(make_train_step(
        cfg, lr_fn=lambda s: wsd_schedule(s, peak_lr=1e-2,
                                          warmup_steps=2,
                                          total_steps=100),
        remat=False).fn)

    def fresh():
        params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        return params, adamw_init(params)

    # uninterrupted 6-step run
    params, opt = fresh()
    for i in range(6):
        params, opt, _ = step_fn(params, opt, pipe.batch(i))
    want = jax.tree.leaves(params)[0]

    # interrupted run: checkpoint at 3, "crash", restore, resume
    ck = Checkpointer(str(tmp_path))
    params, opt = fresh()
    for i in range(3):
        params, opt, _ = step_fn(params, opt, pipe.batch(i))
    ck.save(3, {"params": params, "opt": opt})
    del params, opt                         # the crash

    step = latest_step(str(tmp_path))
    assert step == 3
    p0, o0 = fresh()
    restored = ck.restore(step, {"params": p0, "opt": o0})
    params, opt = restored["params"], restored["opt"]
    for i in range(step, 6):
        params, opt, _ = step_fn(params, opt, pipe.batch(i))
    got = jax.tree.leaves(params)[0]
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

"""The check sees a broken timed path: each fault the cell can have,
planted under the server, turns ``correct`` false."""
import jax.numpy as jnp
import pytest

import bench_tiny
from test_bench_run import LIMITS


def altered_token(engine):
    """An answer altered where it is produced."""
    orig = engine.verify

    def verify(items):
        outs = orig(items)
        for o in outs:
            o.token = (o.token + 1) % engine.cfg.vocab
        return outs

    engine.verify = verify


def state_unchanged(engine):
    """A verify step that leaves the KV pages as they were."""
    orig = engine.verify

    def verify(items):
        kp, vp = jnp.copy(engine.kv.k_pages), jnp.copy(engine.kv.v_pages)
        outs = orig(items)
        engine.kv.k_pages, engine.kv.v_pages = kp, vp
        return outs

    engine.verify = verify


def half_batch(engine):
    """Half of each verify batch (the one row of a one-row batch) left
    out: its rows are never computed and answer "reject all, correct to
    the first draft token"."""
    from repro.serving.engine import VerifyOutcome

    orig = engine.verify

    def verify(items):
        h = len(items) // 2
        outs = orig(items[:h]) if h else []
        return outs + [VerifyOutcome(it.slot, 0, int(it.draft_tokens[0]), 1,
                                     0.0) for it in items[h:]]

    engine.verify = verify


@pytest.mark.parametrize("fault", [altered_token, state_unchanged,
                                   half_batch])
def test_fault_is_not_correct(tmp_path, fault):
    line = bench_tiny.run(bench_tiny.make_root(tmp_path, LIMITS), 5,
                          fault=fault)
    assert line["correct"] is False
    assert any(v["value"] > v["limit"] for v in line["checks"].values())

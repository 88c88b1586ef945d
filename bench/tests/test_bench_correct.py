"""``correct`` at a size a CPU test can hold: sound runs pass, and each
fault a cell can have, planted under the timed path, fails; so does the
control (the reference in bfloat16 in the program's place).

The harness's look for a chip is skipped: ``bench.run.measure`` drives
the rest of a run on the CPU device, at tiny shapes of the same
configurations, with the cells' own limits.
"""
import dataclasses

import jax
import pytest

from bench import compare, control, harness
import bench.run as run

TRAIN = {"kind": "train", "num_envs": 8, "trace_seconds": 0.2}
BENCH = {"end_to_end": [], "per_layer": []}


def tiny(config, n=16, k=4):
    cfg = harness.config_of(config)
    if config == "bitseq120":
        cfg["recipe_env"].update(n=n, k=k)
        cfg["env"].update(n=n, k=k)
    else:
        cfg["recipe_env"].update(dim=2, side=5)
        cfg["env"].update(dim=2, side=5)
        cfg["objective"]["stop_action"] = 2
    return cfg


def context(cell_name, cfg, traffic, seed=2 ** 31 + 5, seconds=0.2):
    cell = {"name": cell_name, "config": cfg["name"], "traffic": "t",
            "chips": 1}
    return run.Context(cell, cfg, traffic, harness.reference_of(cfg["name"]),
                       seed, seconds, False, jax.devices()[:1])


def measure(cell_name, cfg, traffic):
    ctx = context(cell_name, cfg, traffic)
    doc, checks = run.measure(BENCH, ctx.cell, ctx,
                              harness.limits_of(cell_name), {})
    return doc, checks


def step_unchanged(monkeypatch):
    from repro.algo.loop import TrainLoop
    orig = TrainLoop._step_with_eval

    def unchanged(self, state):
        _, out = orig(self, state)
        return state, out
    monkeypatch.setattr(TrainLoop, "_step_with_eval", unchanged)


def half_batch(monkeypatch):
    import repro.algo.loop as loop
    orig = loop.make_loss_parts_fn

    def make(env, policy, cfg):
        fn = orig(env, policy, cfg)

        def parts(params, batch):
            h = batch.log_reward.shape[0] // 2
            return fn(params, jax.tree_util.tree_map(
                lambda x: x[:h] if x.ndim == 1 else x[:, :h], batch))
        return parts
    monkeypatch.setattr(loop, "make_loss_parts_fn", make)


def action_altered(monkeypatch):
    import repro.algo.samplers as samplers
    orig = samplers.forward_rollout

    def rollout(*a, **k):
        b = orig(*a, **k)
        return dataclasses.replace(
            b, actions=b.actions.at[0, 0].set(b.actions[0, 0] ^ 1))
    monkeypatch.setattr(samplers, "forward_rollout", rollout)


@pytest.mark.parametrize("config,cell", [
    ("bitseq120", "bitseq120.train_tb_b16"),
    ("hypergrid20x4", "hypergrid20x4.train_subtb_b16")])
def test_sound_training_is_correct(config, cell):
    doc, checks = measure(cell, tiny(config), TRAIN)
    assert doc["correct"], checks
    assert doc["attempted"] > 0 and doc["failed"] == 0


@pytest.mark.parametrize("fault", [step_unchanged, half_batch,
                                   action_altered])
def test_training_faults_are_caught(fault, monkeypatch):
    fault(monkeypatch)
    doc, checks = measure("bitseq120.train_tb_b16", tiny("bitseq120"),
                          TRAIN)
    assert not doc["correct"], checks


@pytest.mark.parametrize("config,cell", [
    ("bitseq120", "bitseq120.train_tb_b16"),
    ("hypergrid20x4", "hypergrid20x4.train_subtb_b16")])
def test_training_control_and_planted_faults_fail(config, cell):
    ctx = context(cell, tiny(config), TRAIN)
    readings = control.train_readings(
        ctx, harness.kind_driver("train"))
    limits = harness.limits_of(cell)
    assert compare.verdict(readings["sound"], limits)[0], readings
    for name in ("control", "half", "altered"):
        assert not compare.verdict(readings[name], limits)[0], \
            (name, readings[name])


def serve_traffic():
    tr = harness.traffic_of("serve_steady")
    tr.update(rate_per_s=10.0, num_lanes=8, samples_max=8,
              check_samples=32, warmup=[[8, 1.0], [1, 0.5]],
              drain_timeout_s=30.0)
    return tr


def test_sound_serving_is_correct():
    doc, checks = measure("bitseq120.serve_steady", tiny("bitseq120"),
                          serve_traffic())
    assert doc["correct"], checks
    assert doc["attempted"] > 0 and doc["failed"] == 0


def test_serving_fault_is_caught(monkeypatch):
    import repro.serve.front as front
    orig = front.result_from_engine

    def altered(req, res, rid):
        r = orig(req, res, rid)
        samples = [list(s) for s in r.samples]
        samples[0][0] ^= 1
        return dataclasses.replace(r, samples=samples)
    monkeypatch.setattr(front, "result_from_engine", altered)
    doc, checks = measure("bitseq120.serve_steady", tiny("bitseq120"),
                          serve_traffic())
    assert not doc["correct"], checks


def test_serving_control_fails():
    # 1280 actions and 2560 served words: enough draws for bfloat16 to
    # put another word first somewhere
    ctx = context("bitseq120.serve_steady", tiny("bitseq120", n=40, k=8),
                  dict(serve_traffic(), rate_per_s=20.0, samples_max=64,
                       check_samples=512), seconds=2.0)
    readings = control.serve_readings(ctx, harness.kind_driver("serve"))
    limits = harness.limits_of("bitseq120.serve_steady")
    assert compare.verdict(readings["sound"], limits)[0], readings
    assert readings["control"]["gap"] > limits["gap"], readings

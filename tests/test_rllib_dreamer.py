"""Dreamer (world model + imagination AC) tests.

Reference analog: rllib/algorithms/dreamerv3/tests — world-model
learning, imagined-rollout machinery, and the Algorithm surface
(train/checkpoint). Learning assertions target the WORLD MODEL
(reward/recon/continue losses falling on a predictable env) — the
cheapest falsifiable signal of the architecture working; full policy
convergence is a release-scale test, not a CI one.
"""

import numpy as np

import ray_tpu
from ray_tpu.rllib import Dreamer, DreamerConfig
from ray_tpu.rllib.dreamer import (
    DreamerHyperparams,
    DreamerLearner,
    DreamerModelConfig,
    SequenceReplay,
    build_dreamer_policy,
    symexp,
    symlog,
)
from ray_tpu.rllib.env_runner import Episode


class ChainEnv:
    """Walk right along a one-hot chain; +1 at the end, -0.01/step —
    fully deterministic, so the world model's reward/transition heads
    have an exact function to learn."""

    N = 6

    def __init__(self):
        self.pos = 0
        self.t = 0

    def _obs(self):
        o = np.zeros(self.N, np.float32)
        o[self.pos] = 1.0
        return o

    def reset(self, seed=None):
        self.pos, self.t = 0, 0
        return self._obs(), {}

    def step(self, action):
        self.t += 1
        self.pos = max(0, min(self.N - 1,
                              self.pos + (1 if action == 1 else -1)))
        term = self.pos == self.N - 1
        reward = 1.0 if term else -0.01
        trunc = self.t >= 20 and not term
        return self._obs(), reward, term, trunc, {}


def _random_episodes(n, rng):
    """Random-policy ChainEnv episodes (world-model training data)."""
    eps = []
    env = ChainEnv()
    for _ in range(n):
        obs, _ = env.reset()
        ep = Episode()
        done = False
        while not done:
            a = int(rng.integers(2))
            nxt, r, term, trunc, _ = env.step(a)
            ep.obs.append(obs)
            ep.actions.append(a)
            ep.rewards.append(r)
            ep.logps.append(0.0)
            ep.values.append(0.0)
            obs = nxt
            done = term or trunc
        ep.terminated, ep.truncated = term, trunc
        ep.final_obs = obs
        eps.append(ep)
    return eps


def test_symlog_roundtrip():
    import jax.numpy as jnp
    x = jnp.asarray([-100.0, -1.0, 0.0, 0.5, 30.0])
    np.testing.assert_allclose(np.asarray(symexp(symlog(x))),
                               np.asarray(x), rtol=1e-5, atol=1e-5)


def test_sequence_replay_segments_and_is_first():
    rng = np.random.default_rng(0)
    buf = SequenceReplay(capacity_steps=10_000, seq_len=8)
    buf.add_episodes(_random_episodes(6, rng))
    batch = buf.sample(4, rng)
    assert batch["obs"].shape == (4, 8, ChainEnv.N)
    assert batch["actions"].shape == (4, 8)
    assert set(batch) == {"obs", "actions", "rewards", "cont",
                          "is_first"}
    # is_first is only ever set on a segment's step 0, and only when
    # the segment starts at the episode head.
    assert (batch["is_first"][:, 1:] == 0).all()


def test_world_model_learns_reward_and_recon():
    """On the deterministic chain, a few dozen updates must drive
    reward/recon losses well below their initial values — the
    falsifiable core of the world model."""
    rng = np.random.default_rng(0)
    cfg = DreamerModelConfig(obs_dim=ChainEnv.N, num_actions=2,
                             embed=32, deter=32, n_cat=4,
                             n_classes=4, hidden=32)
    hp = DreamerHyperparams(batch_size=8, seq_len=8, horizon=5,
                            wm_lr=1e-3)
    learner = DreamerLearner(cfg, hp, seed=0)
    buf = SequenceReplay(10_000, hp.seq_len)
    buf.add_episodes(_random_episodes(40, rng))

    import jax
    import jax.numpy as jnp

    # Deterministic learning signal: evaluate the SAME held-out batch
    # with the SAME latent-sampling key before and after training —
    # per-update metrics bounce with the sparse terminal rewards in
    # each sampled batch, a fixed eval batch does not.
    eval_np = buf.sample(32, rng)
    eval_mb = {k: jnp.asarray(v) for k, v in eval_np.items()}
    eval_key = jax.random.key(123)

    def eval_losses():
        _t, (aux, _out) = learner._wm_loss(learner.params, eval_mb,
                                           eval_key)
        return {k: float(v) for k, v in aux.items()}

    before = eval_losses()
    last = {}
    for _ in range(120):
        last = learner.update(buf.sample(hp.batch_size, rng))
    after = eval_losses()

    # Terminal (+1) rewards are ~1/20 of steps, so the reward head
    # converges slower than recon/cont — 35%+ off a fixed batch in
    # 120 updates is the robust signal.
    assert after["reward_loss"] < before["reward_loss"] * 0.65, (
        before, after)
    assert after["recon_loss"] < before["recon_loss"] * 0.6
    assert after["cont_loss"] < before["cont_loss"] * 0.5
    assert np.isfinite(after["wm_loss"])
    assert np.isfinite(last["actor_loss"])
    assert np.isfinite(last["imag_return"])


def test_rollout_policy_protocol():
    """The EnvRunner-facing adapter: carry advances, feed_action
    installs the chosen action, logits/value have policy shapes."""
    import jax

    pol = build_dreamer_policy({"obs_dim": 4, "num_actions": 3,
                                "deter": 16, "n_cat": 2,
                                "n_classes": 4, "embed": 16,
                                "hidden": 16})
    params = pol.init_params(jax.random.key(0))
    carry = pol.initial_state(1)
    obs = np.zeros((1, 4), np.float32)
    logits, value, carry2 = pol.apply({"params": params}, obs, carry)
    assert logits.shape == (1, 3) and value.shape == (1,)
    # action slot is zeroed until feed_action installs the choice
    assert float(np.abs(np.asarray(carry2[2])).sum()) == 0.0
    carry3 = pol.feed_action(carry2, 2)
    onehot = np.asarray(carry3[2])[0]
    assert onehot[2] == 1.0 and onehot.sum() == 1.0
    # deterministic mode path: same obs+carry -> same latent
    l2, _v, _c = pol.apply({"params": params}, obs, carry)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(l2))


def test_dreamer_end_to_end_and_checkpoint(tmp_path):
    """Algorithm surface: train() iterations through real EnvRunner
    actors, then a Checkpointable save/restore round-trip resumes at
    iteration+1 with identical params."""
    import jax

    ray_tpu.init(num_cpus=4)
    try:
        config = (DreamerConfig()
                  .environment(ChainEnv, obs_dim=ChainEnv.N,
                               num_actions=2, deter=32, n_cat=4,
                               n_classes=4, embed=32, hidden=32)
                  .env_runners(1)
                  .training(learning_starts=60, batch_size=4,
                            seq_len=8, horizon=5,
                            wm_updates_per_iter=2))
        algo = config.build()
        for _ in range(3):
            result = algo.train()
        assert result["training_iteration"] == 3
        assert result["buffer_steps"] >= 60
        assert "wm_loss" in result        # learning actually started

        path = str(tmp_path / "ckpt")
        algo.save_to_path(path)
        algo.stop()

        restored = config.build()
        restored.restore_from_path(path)
        assert restored.iteration == 3
        p0 = jax.tree_util.tree_leaves(restored.learner.params)[0]
        assert np.isfinite(np.asarray(p0)).all()
        result = restored.train()
        assert result["training_iteration"] == 4
        restored.stop()
    finally:
        ray_tpu.shutdown()

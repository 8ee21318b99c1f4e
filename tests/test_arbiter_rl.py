"""Arbiter hyperparameter search + RL4J-parity DQN/A2C.

Reference test parity: arbiter's optimization runner tests and rl4j's
SimpleToy-based learning tests (SURVEY.md §2.2 J21)."""

import math

import numpy as np
import pytest

from deeplearning4j_tpu.arbiter import (
    ContinuousParameterSpace,
    DiscreteParameterSpace,
    FixedValue,
    GridSearchCandidateGenerator,
    IntegerParameterSpace,
    MaxCandidatesCondition,
    OptimizationRunner,
    RandomSearchGenerator,
)
from deeplearning4j_tpu.rl4j import (
    A2CConfiguration,
    A2CDiscreteDense,
    CartPole,
    QLearningConfiguration,
    QLearningDiscreteDense,
    SimpleToyMDP,
)


class TestArbiter:
    def test_spaces_sample_within_bounds(self):
        rng = np.random.default_rng(0)
        c = ContinuousParameterSpace(1e-4, 1e-1, log_scale=True)
        assert all(1e-4 <= c.sample(rng) <= 1e-1 for _ in range(50))
        i = IntegerParameterSpace(3, 7)
        assert set(i.grid(10)) == {3, 4, 5, 6, 7}
        d = DiscreteParameterSpace("a", "b")
        assert d.sample(rng) in ("a", "b")

    def test_random_search_finds_minimum(self):
        space = {"x": ContinuousParameterSpace(-2.0, 2.0),
                 "tag": FixedValue("v")}
        runner = OptimizationRunner(
            space, RandomSearchGenerator(64, seed=1),
            model_builder=lambda c: c,
            score_fn=lambda c: (c["x"] - 0.5) ** 2,
            minimize=True)
        res = runner.execute()
        assert abs(res.best_candidate["x"] - 0.5) < 0.2
        assert len(res.results) == 64
        assert res.best_candidate["tag"] == "v"

    def test_grid_search_enumerates_product(self):
        space = {"a": IntegerParameterSpace(0, 1),
                 "b": DiscreteParameterSpace("x", "y", "z")}
        runner = OptimizationRunner(
            space, GridSearchCandidateGenerator(),
            model_builder=lambda c: c, score_fn=lambda c: 0.0)
        res = runner.execute()
        assert len(res.results) == 6

    def test_failed_candidates_recorded_not_fatal(self):
        def build(c):
            if c["x"] > 0:
                raise RuntimeError("bad config")
            return c

        runner = OptimizationRunner(
            {"x": DiscreteParameterSpace(-1, 1)},
            GridSearchCandidateGenerator(),
            model_builder=build, score_fn=lambda c: c["x"])
        res = runner.execute()
        errs = [r for r in res.results if r.error]
        assert len(errs) == 1 and math.isnan(errs[0].score)
        assert res.best_candidate == {"x": -1}

    def test_termination_condition(self):
        runner = OptimizationRunner(
            {"x": ContinuousParameterSpace(0, 1)},
            RandomSearchGenerator(100, seed=0),
            model_builder=lambda c: c, score_fn=lambda c: c["x"],
            termination_conditions=[MaxCandidatesCondition(5)])
        assert len(runner.execute().results) == 5

    def test_network_hyperparam_search(self, rng):
        from deeplearning4j_tpu.nn import (
            InputType, MultiLayerNetwork, NeuralNetConfiguration)
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.updaters import Adam

        xs = rng.standard_normal((64, 4)).astype(np.float32)
        ys = np.eye(2, dtype=np.float32)[(xs.sum(1) > 0).astype(int)]

        def build(c):
            conf = (NeuralNetConfiguration.builder().seed(0)
                    .updater(Adam(c["lr"])).list()
                    .layer(DenseLayer(n_in=4, n_out=c["hidden"], activation="relu"))
                    .layer(OutputLayer(n_in=c["hidden"], n_out=2, loss="mcxent",
                                       activation="softmax"))
                    .set_input_type(InputType.feed_forward(4)).build())
            return MultiLayerNetwork(conf).init().fit(xs, ys, epochs=30)

        res = OptimizationRunner(
            {"lr": DiscreteParameterSpace(1e-4, 1e-2),
             "hidden": IntegerParameterSpace(8, 16)},
            RandomSearchGenerator(4, seed=0),
            model_builder=build,
            score_fn=lambda net: net.score(x=xs, y=ys)).execute()
        assert res.best_score < 0.6
        assert res.best_model is not None


class TestRL:
    def test_dqn_learns_toy_chain(self):
        mdp = SimpleToyMDP(length=6)
        conf = QLearningConfiguration(
            max_step=4000, epsilon_nb_step=1500, batch_size=32,
            hidden=(32,), target_dqn_update_freq=50, seed=1)
        learner = QLearningDiscreteDense(mdp, conf).train()
        policy = learner.get_policy()
        # optimal play walks the chain: reward 0.1*(L-1) + 1.0
        total = policy.play(SimpleToyMDP(length=6))
        assert total >= 1.0, total

    def test_double_dqn_learns_toy_chain(self):
        """rl4j doubleDQN parity: online-argmax / target-eval bootstrap
        (DoubleDQN target computer) must also solve the chain."""
        mdp = SimpleToyMDP(length=6)
        conf = QLearningConfiguration(
            max_step=4000, epsilon_nb_step=1500, batch_size=32,
            hidden=(32,), target_dqn_update_freq=50, seed=1,
            double_dqn=True)
        learner = QLearningDiscreteDense(mdp, conf).train()
        total = learner.get_policy().play(SimpleToyMDP(length=6))
        assert total >= 1.0, total

    def test_double_dqn_target_math(self):
        """The double-DQN target must use Q_target at the ONLINE argmax —
        distinguishable from max(Q_target) when the two nets disagree."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.rl4j import dqn as D

        mdp = SimpleToyMDP(length=4)
        conf = QLearningConfiguration(hidden=(8,), seed=0, double_dqn=True,
                                      gamma=1.0, reward_factor=1.0)
        learner = QLearningDiscreteDense(mdp, conf)
        # force disagreement: negate ONLY the output layer, so
        # q_target == -q_online exactly and argmax(target) == argmin(online)
        # on every row (negating every layer — the old construction — runs
        # the negated weights through relu, which happens to preserve the
        # argmax for this seed and made the sanity check below flaky)
        learner.target_params = learner.params[:-1] + [
            jax.tree_util.tree_map(lambda x: -x, learner.params[-1])]
        s2 = jnp.asarray(np.random.default_rng(0).normal(
            size=(3, mdp.obs_size)).astype(np.float32))
        q_online = D._mlp_apply(learner.params, s2)
        q_target = D._mlp_apply(learner.target_params, s2)
        a_star = jnp.argmax(q_online, axis=-1)
        expected = jnp.take_along_axis(q_target, a_star[:, None], 1)[:, 0]
        standard = jnp.max(q_target, axis=-1)
        # sanity: the two targets differ on this construction
        assert not np.allclose(expected, standard)
        # one train call must run without error under the flag
        s = jnp.zeros((3, mdp.obs_size))
        a = jnp.zeros((3,), jnp.int32)
        r = jnp.ones((3,))
        done = jnp.zeros((3,))
        learner._train(learner.params, learner.target_params,
                       learner.opt_state, jnp.asarray(0), s, a, r, s2, done)

    # slow: 8,000 environment steps; test_dqn_learns_toy_chain and
    # test_double_dqn_learns_toy_chain above are the tier-1 tests that the
    # same learner learns
    @pytest.mark.slow
    def test_dqn_cartpole_improves(self):
        conf = QLearningConfiguration(
            max_step=8000, epsilon_nb_step=4000, batch_size=64,
            hidden=(64, 64), target_dqn_update_freq=200, seed=0)
        learner = QLearningDiscreteDense(CartPole(seed=0), conf).train()
        policy = learner.get_policy()
        score = np.mean([policy.play(CartPole(seed=s)) for s in range(5)])
        assert score > 100, score  # random policy scores ~20

    def test_a2c_learns_toy_chain(self):
        conf = A2CConfiguration(max_updates=300, num_envs=4, n_steps=8,
                                hidden=(32,), seed=0)
        learner = A2CDiscreteDense(lambda: SimpleToyMDP(length=6), conf).train()
        total = learner.get_policy().play(SimpleToyMDP(length=6))
        assert total >= 1.0, total


class TestA3C:
    @pytest.mark.slow
    def test_a3c_async_learns_toy_chain(self):
        """ASYNC A3C (VERDICT r3 J21 tail): 4 actor-learner threads, stale
        gradients, shared Adam under a lock — learns the toy chain.

        slow: half a minute of four Python threads taking turns at one
        lock. In tier-1 test_a2c_learns_toy_chain is the same
        actor-critic update without the threads, and test_dqn_learns_toy_chain
        the toy chain."""
        from deeplearning4j_tpu.rl4j import A3CConfiguration, A3CDiscreteDense

        conf = A3CConfiguration(max_updates=400, num_threads=4, n_steps=8,
                                hidden=(32,), seed=0)
        learner = A3CDiscreteDense(lambda: SimpleToyMDP(length=6),
                                   conf).train()
        assert learner._updates_done >= conf.max_updates
        total = learner.get_policy().play(SimpleToyMDP(length=6))
        assert total >= 1.0, total


class TestGeneticSearch:
    def test_genetic_beats_its_first_generation(self):
        """GeneticSearchCandidateGenerator parity: population breeding must
        IMPROVE across generations on a smooth objective (and beat plain
        random search at equal budget)."""
        from deeplearning4j_tpu.arbiter import (
            GeneticSearchCandidateGenerator,
            OptimizationRunner,
        )

        space = {"x": ContinuousParameterSpace(-4.0, 4.0),
                 "y": ContinuousParameterSpace(-4.0, 4.0)}

        def objective(c):
            return (c["x"] - 1.0) ** 2 + (c["y"] + 2.0) ** 2

        gen = GeneticSearchCandidateGenerator(
            population_size=10, generations=8, seed=3)
        runner = OptimizationRunner(
            space, gen, model_builder=lambda c: c,
            score_fn=objective, minimize=True)
        res = runner.execute()
        pop = gen.population_size
        first_gen_best = min(r.score for r in res.results[:pop])
        assert res.best_score < first_gen_best, \
            (res.best_score, first_gen_best)
        assert res.best_score < 0.15, res.best_score

        rnd = RandomSearchGenerator(num_candidates=pop * 8, seed=3)
        rnd_runner = OptimizationRunner(
            space, rnd, model_builder=lambda c: c, score_fn=objective,
            minimize=True)
        rnd_best = rnd_runner.execute().best_score
        assert res.best_score <= rnd_best, (res.best_score, rnd_best)

    def test_genetic_survives_failing_candidates(self):
        from deeplearning4j_tpu.arbiter import (
            GeneticSearchCandidateGenerator,
            OptimizationRunner,
        )

        space = {"x": ContinuousParameterSpace(-1.0, 1.0)}
        calls = []

        def flaky(c):
            calls.append(c)
            if len(calls) % 3 == 0:
                raise RuntimeError("boom")
            return c["x"] ** 2

        gen = GeneticSearchCandidateGenerator(
            population_size=6, generations=3, seed=0)
        res = OptimizationRunner(space, gen, model_builder=lambda c: c,
                                 score_fn=flaky, minimize=True).execute()
        assert res.best_candidate is not None
        assert sum(1 for r in res.results if r.error) > 0

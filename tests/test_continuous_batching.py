"""Continuous-batching engine vs the batch-synchronous generator: the
slot machinery (chunked prefill, in-graph refill, EOS stop) must be
invisible in the outputs — greedy decode of each prompt must match
``generate`` run on that prompt alone."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import tiny
from dlrover_tpu.models.transformer import init_params
from dlrover_tpu.rl.continuous_batching import continuous_generate
from dlrover_tpu.rl.generation import _mask_logits, generate


@pytest.fixture(scope="module")
def model():
    cfg = tiny(vocab_size=61, num_layers=2, max_seq_len=64)
    params = jax.jit(lambda k: init_params(k, cfg))(jax.random.PRNGKey(3))
    return cfg, params


def _prompt_queue(n, p_max, vocab, seed=0):
    """n prompts of varied lengths 2..p_max, right-padded."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(2, p_max + 1, size=n)
    toks = np.zeros((n, p_max), np.int32)
    for i, ln in enumerate(lens):
        toks[i, :ln] = rng.integers(1, vocab, size=ln)
    return jnp.asarray(toks), jnp.asarray(lens.astype(np.int32))


class TestGreedyEquivalence:
    @pytest.mark.slow  # ~9s
    def test_matches_single_prompt_generate(self, model):
        cfg, params = model
        N, P_max, new = 5, 10, 6
        prompts, lens = _prompt_queue(N, P_max, cfg.vocab_size)
        out_tokens, out_logps, out_lens = continuous_generate(
            params, prompts, lens, jax.random.PRNGKey(0), cfg,
            max_new_tokens=new, slots=2, greedy=True,
        )
        for i in range(N):
            ln = int(lens[i])
            ref_tokens, ref_logps = generate(
                params, prompts[i : i + 1, :ln], jax.random.PRNGKey(0),
                cfg, max_new_tokens=new, greedy=True,
            )
            assert int(out_lens[i]) == ln + new
            np.testing.assert_array_equal(
                np.asarray(out_tokens[i, : ln + new]),
                np.asarray(ref_tokens[0]),
            )
            np.testing.assert_allclose(
                np.asarray(out_logps[i]),
                np.asarray(ref_logps[0]),
                rtol=2e-4, atol=2e-5,
            )

    @pytest.mark.slow  # ~10s; refill path also covered by determinism tests
    def test_more_prompts_than_slots_refills(self, model):
        # N >> slots forces multiple refill waves through one slot
        cfg, params = model
        N, P_max, new = 9, 6, 4
        prompts, lens = _prompt_queue(N, P_max, cfg.vocab_size, seed=7)
        out_tokens, _, out_lens = continuous_generate(
            params, prompts, lens, jax.random.PRNGKey(0), cfg,
            max_new_tokens=new, slots=2, greedy=True,
        )
        for i in range(N):
            ln = int(lens[i])
            ref_tokens, _ = generate(
                params, prompts[i : i + 1, :ln], jax.random.PRNGKey(0),
                cfg, max_new_tokens=new, greedy=True,
            )
            np.testing.assert_array_equal(
                np.asarray(out_tokens[i, : ln + new]),
                np.asarray(ref_tokens[0]),
            )


class TestEos:
    def test_stops_at_eos_and_keeps_it(self, model):
        cfg, params = model
        N, P_max, new = 3, 8, 6
        prompts, lens = _prompt_queue(N, P_max, cfg.vocab_size, seed=1)
        # find what greedy decode produces for prompt 0, pick its 3rd
        # generated token as "EOS"
        ln0 = int(lens[0])
        ref_tokens, _ = generate(
            params, prompts[0:1, :ln0], jax.random.PRNGKey(0), cfg,
            max_new_tokens=new, greedy=True,
        )
        eos = int(ref_tokens[0, ln0 + 2])
        out_tokens, out_logps, out_lens = continuous_generate(
            params, prompts, lens, jax.random.PRNGKey(0), cfg,
            max_new_tokens=new, slots=3, greedy=True, eos_id=eos,
        )
        # prompt 0 must stop right after emitting the EOS token
        assert int(out_lens[0]) == ln0 + 3
        assert int(out_tokens[0, ln0 + 2]) == eos
        # logps past the stop are zero-padded
        np.testing.assert_array_equal(
            np.asarray(out_logps[0, 3:]), np.zeros(new - 3, np.float32)
        )
        # other prompts keep their full budget unless they also hit eos
        for i in range(1, N):
            assert int(out_lens[i]) <= int(lens[i]) + new

    def test_no_eos_runs_full_budget(self, model):
        cfg, params = model
        N, P_max, new = 4, 6, 5
        prompts, lens = _prompt_queue(N, P_max, cfg.vocab_size, seed=2)
        _, _, out_lens = continuous_generate(
            params, prompts, lens, jax.random.PRNGKey(0), cfg,
            max_new_tokens=new, slots=4, greedy=True,
        )
        np.testing.assert_array_equal(
            np.asarray(out_lens), np.asarray(lens) + new
        )


class TestSampled:
    def test_sampling_respects_support_restriction(self, model):
        # top_k=1 sampling == greedy decode, regardless of temperature
        cfg, params = model
        N, P_max, new = 4, 6, 4
        prompts, lens = _prompt_queue(N, P_max, cfg.vocab_size, seed=5)
        out_g, _, _ = continuous_generate(
            params, prompts, lens, jax.random.PRNGKey(0), cfg,
            max_new_tokens=new, slots=2, greedy=True,
        )
        out_k1, _, _ = continuous_generate(
            params, prompts, lens, jax.random.PRNGKey(0), cfg,
            max_new_tokens=new, slots=2, temperature=0.7, top_k=1,
        )
        np.testing.assert_array_equal(
            np.asarray(out_g), np.asarray(out_k1)
        )

    def test_rejects_bad_knobs(self, model):
        cfg, params = model
        prompts, lens = _prompt_queue(2, 4, cfg.vocab_size)
        with pytest.raises(ValueError, match="top_p"):
            continuous_generate(
                params, prompts, lens, jax.random.PRNGKey(0), cfg,
                top_p=0.0,
            )


class TestMaskLogits:
    """Edge cases of the vLLM-style support restriction: top_k=0 and
    top_p=1.0 are keep-all, the nucleus boundary token stays in, and
    composed knobs renormalize over the top-k restriction first."""

    def _logits(self, probs):
        # softmax(log p) == p, so tests can reason in probabilities
        return jnp.log(jnp.asarray([probs], jnp.float32))

    def test_topk_zero_topp_one_is_identity(self):
        logits = jnp.asarray([[0.5, -1.0, 2.0, 0.0]], jnp.float32)
        out = _mask_logits(logits, 0, 1.0)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(logits))

    def test_topk_larger_than_vocab_clamps_to_keep_all(self):
        logits = jnp.asarray([[0.5, -1.0, 2.0, 0.0]], jnp.float32)
        out = _mask_logits(logits, 99, 1.0)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(logits))

    def test_topk_only_keeps_exactly_k(self):
        logits = jnp.asarray([[0.1, 3.0, 2.0, -1.0, 0.5]], jnp.float32)
        out = np.asarray(_mask_logits(logits, 2, 1.0))
        finite = np.isfinite(out[0])
        assert set(np.nonzero(finite)[0]) == {1, 2}
        np.testing.assert_array_equal(out[0][finite], [3.0, 2.0])

    def test_nucleus_boundary_token_stays(self):
        # probs .5/.3/.15/.05, p=0.6: keep while PRECEDING mass < p —
        # token 1 crosses 0.6 and stays (the nucleus definition);
        # token 2's preceding mass is 0.8, out
        out = np.asarray(_mask_logits(self._logits([0.5, 0.3, 0.15, 0.05]), 0, 0.6))
        np.testing.assert_array_equal(
            np.isfinite(out[0]), [True, True, False, False]
        )

    def test_nucleus_tiny_p_keeps_argmax(self):
        out = np.asarray(_mask_logits(self._logits([0.2, 0.5, 0.3]), 0, 1e-6))
        np.testing.assert_array_equal(
            np.isfinite(out[0]), [False, True, False]
        )

    def test_topk_then_nucleus_composes_renormalized(self):
        # probs .4/.3/.2/.1 with top_k=2, top_p=0.5: the nucleus runs
        # over the RESTRICTED renormalized distribution [.571, .429] —
        # token 1's preceding mass is .571 >= .5, so only token 0
        # survives. Nucleus alone at p=0.5 would keep two tokens.
        logits = self._logits([0.4, 0.3, 0.2, 0.1])
        combined = np.asarray(_mask_logits(logits, 2, 0.5))
        np.testing.assert_array_equal(
            np.isfinite(combined[0]), [True, False, False, False]
        )
        nucleus_only = np.asarray(_mask_logits(logits, 0, 0.5))
        np.testing.assert_array_equal(
            np.isfinite(nucleus_only[0]), [True, True, False, False]
        )

    def test_rows_masked_independently(self):
        logits = jnp.log(jnp.asarray(
            [[0.5, 0.3, 0.15, 0.05], [0.05, 0.15, 0.3, 0.5]], jnp.float32
        ))
        out = np.asarray(_mask_logits(logits, 0, 0.6))
        np.testing.assert_array_equal(
            np.isfinite(out[0]), [True, True, False, False]
        )
        np.testing.assert_array_equal(
            np.isfinite(out[1]), [False, False, True, True]
        )


class TestDeterministicSeeds:
    """Sampling inside ``continuous_generate`` folds the key per decode
    step: the whole rollout is a pure function of (params, prompts,
    key) — the serving plane relies on this for replayable decodes."""

    def test_same_key_bitwise_identical(self, model):
        cfg, params = model
        prompts, lens = _prompt_queue(4, 6, cfg.vocab_size, seed=9)
        runs = [
            continuous_generate(
                params, prompts, lens, jax.random.PRNGKey(42), cfg,
                max_new_tokens=4, slots=2, temperature=0.8,
                top_k=5, top_p=0.9,
            )
            for _ in range(2)
        ]
        for a, b in zip(runs[0], runs[1]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_different_key_differs(self, model):
        cfg, params = model
        prompts, lens = _prompt_queue(4, 6, cfg.vocab_size, seed=9)
        out = [
            continuous_generate(
                params, prompts, lens, jax.random.PRNGKey(k), cfg,
                max_new_tokens=4, slots=2, temperature=0.8,
                top_k=5, top_p=0.9,
            )[0]
            for k in (42, 43)
        ]
        assert not np.array_equal(np.asarray(out[0]), np.asarray(out[1]))

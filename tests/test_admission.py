"""Jitted admission on small real models: the batcher serves the same
tokens as an eager prefill and an eager splice, and its splice program
declares the batch cache donated.

The eager batcher below is the admission the jitted programs replaced:
``api.prefill`` run op by op, the first token read at ``true_len - 1``,
and a ``dynamic_update_slice`` of every cache leaf outside any jit.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.analysis.compiled import check_donation  # noqa: E402
from repro.analysis.compiled.hlo_lint import parse_declared_donors  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import api  # noqa: E402
from repro.serving import scheduler as sched  # noqa: E402
from repro.serving.decode import generate  # noqa: E402

#: an SSM, an attention model, and a hybrid whose cache holds KV and SSM
#: state side by side in one scanned period
ARCHS = ["mamba2-370m", "llama3.2-1b", "granite-4.0-h-small"]
SLOTS = 2
MAX_LEN = 2 * sched.PREFILL_BUCKET + 16


class _EagerBatcher(sched.ContinuousBatcher):
    def _admit_one(self, req, slot):
        true_len = len(req.prompt)
        ids = np.zeros((1, sched.bucket_len(true_len, self.max_len)),
                       np.int32)
        ids[0, :true_len] = req.prompt
        logits, cache1 = api.prefill(self.params, self.cfg, self.max_len,
                                     tokens=jnp.asarray(ids))
        tok = int(jnp.argmax(logits[0, true_len - 1]))
        req.generated.append(tok)
        if tok == self.eos_id or len(req.generated) >= req.max_new_tokens:
            self._retire(req)
            return False

        def splice(batch_leaf, one_leaf):
            if batch_leaf.ndim == 0 or one_leaf.shape == batch_leaf.shape:
                return batch_leaf
            for ax in range(batch_leaf.ndim):
                if batch_leaf.shape[ax] == self.num_slots and \
                        one_leaf.shape[ax] == 1:
                    return jax.lax.dynamic_update_slice_in_dim(
                        batch_leaf, one_leaf.astype(batch_leaf.dtype),
                        slot, axis=ax)
            return batch_leaf
        new_cache = jax.tree.map(splice, dict(self.cache), dict(cache1))
        new_cache["len"] = self.cache["len"]
        self.cache = new_cache
        self.tokens = self.tokens.at[slot, 0].set(tok)
        self.slots[slot] = req
        self._slot_len[slot] = true_len
        return True


def _model(arch):
    cfg = get_config(arch, reduced=True)
    return cfg, api.init_params(jax.random.PRNGKey(7), cfg)


def _serve(cls, params, cfg, prompts, max_new, slots=SLOTS):
    b = cls(params, cfg, num_slots=slots, max_len=MAX_LEN, eos_id=-1)
    uids = [b.submit(p, max_new_tokens=n) for p, n in zip(prompts, max_new)]
    done = {r.uid: r.generated for r in b.run_until_drained()}
    return [done[u] for u in uids], b


@pytest.mark.parametrize("arch", ARCHS)
def test_jitted_admission_serves_the_eager_tokens(arch):
    """Five bucket-filling prompts of two buckets through two slots,
    one of them done at prefill: the same tokens as eager admission, and
    one prefill per bucket plus one splice traced."""
    cfg, params = _model(arch)
    rng = np.random.default_rng(3)
    lens = [sched.PREFILL_BUCKET, 2 * sched.PREFILL_BUCKET,
            sched.PREFILL_BUCKET, 2 * sched.PREFILL_BUCKET,
            sched.PREFILL_BUCKET]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    max_new = [4, 3, 1, 5, 4]
    want, _ = _serve(_EagerBatcher, params, cfg, prompts, max_new)
    got, b = _serve(sched.ContinuousBatcher, params, cfg, prompts, max_new)
    assert got == want
    assert [len(g) for g in got] == max_new
    assert b.traces == 3


@pytest.mark.parametrize("arch", ARCHS)
def test_one_slot_serves_the_tokens_of_generate(arch):
    """Regression: with one slot the batch cache and the prefill's cache
    have the same shapes, and the splice used to keep the empty batch
    cache, so the request decoded from no context at all."""
    cfg, params = _model(arch)
    prompt = np.random.default_rng(5).integers(
        0, cfg.vocab_size, sched.PREFILL_BUCKET).astype(np.int32)
    want = generate(params, cfg, jnp.asarray(prompt[None]), 5,
                    max_len=MAX_LEN)
    got, _ = _serve(sched.ContinuousBatcher, params, cfg, [prompt], [5],
                    slots=1)
    assert got == [np.asarray(want)[0].tolist()]


@pytest.mark.parametrize("arch", ARCHS)
def test_splice_declares_the_batch_cache_donated(arch):
    cfg, params = _model(arch)
    b = sched.ContinuousBatcher(params, cfg, num_slots=SLOTS,
                                max_len=MAX_LEN, eos_id=-1)
    ids = np.zeros((1, sched.PREFILL_BUCKET), np.int32)
    tok, cache1 = b._prefill(params, ids, np.int32(sched.PREFILL_BUCKET))
    lowered = b._splice.lower(b.cache, cache1, b.tokens, np.int32(1), tok)
    text = lowered.compile().as_text()
    assert check_donation(text, subject=arch, site="splice",
                          lowered_text=lowered.as_text()) == []
    # arguments flatten in order, the batch cache's leaves first; the
    # one other donor is the token column (unused arguments are pruned,
    # so its number depends on the prefill cache's unused leaves)
    n_cache = len(jax.tree.leaves(b.cache))
    donors = parse_declared_donors(lowered.as_text())
    assert set(range(n_cache)) <= donors and len(donors) == n_cache + 1


def test_splice_raises_on_a_leaf_without_a_batch_axis():
    """A mixed KV and SSM cache splices every leaf into its slot; a leaf
    with no axis of the slot count is an error, not a leaf left as it
    was."""
    cfg, params = _model("granite-4.0-h-small")
    b = sched.ContinuousBatcher(params, cfg, num_slots=SLOTS,
                                max_len=MAX_LEN, eos_id=-1)
    kinds = {type(leaf).__name__ for leaf in b.cache["slots"].values()}
    assert kinds == {"dict", "SSMState"}
    ids = np.arange(sched.PREFILL_BUCKET, dtype=np.int32)[None]
    tok, cache1 = b._prefill(params, ids, np.int32(sched.PREFILL_BUCKET))
    want = jax.tree.map(np.asarray, cache1)
    cache, _ = b._splice(b.cache, cache1, b.tokens, np.int32(1), tok)
    # slot 1 of every leaf is the request's; slot 0 is still empty
    for got, one in zip(jax.tree.leaves(cache["slots"]),
                        jax.tree.leaves(want["slots"])):
        np.testing.assert_array_equal(np.asarray(got[:, 1:2]), one)
        assert not np.asarray(got[:, 0]).any()

    # (the splice donated the batch cache and tokens: build fresh ones)
    bad = dict(api.init_cache(cfg, SLOTS, MAX_LEN), extra=jnp.zeros((3,)))
    bad1 = dict(cache1, extra=jnp.zeros((3,)))
    with pytest.raises(ValueError, match="cannot splice cache leaf"):
        b._splice(bad, bad1, jnp.zeros((SLOTS, 1), jnp.int32), np.int32(0),
                  tok)

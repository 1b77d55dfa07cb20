"""ValidatorSet.hash() is computed once per validators list.

The root is memoised on the list object (as _pub_matrix and the address
index are), carried by copy(), dropped by update_with_change_set and
left behind by every codec.  Held here to the plain recomputation from
the leaves, after every kind of step a set goes through and after seeded
random sequences of them.
"""
import pickle
import random

import pytest

from tendermint_tpu.crypto import ed25519 as edkeys
from tendermint_tpu.crypto import merkle
from tendermint_tpu.libs import safe_codec
from tendermint_tpu.types.validator import Validator
from tendermint_tpu.types.validator_set import ValidatorSet


def _val(i, power):
    return Validator.new(edkeys.PrivKey(i.to_bytes(32, "big")).pub_key(),
                         power)


def _from_scratch(vs):
    """The unmemoised hash as it was: every leaf, every call."""
    return merkle.hash_from_byte_slices([v.bytes() for v in vs.validators])


def _assert_root(vs):
    want = _from_scratch(vs)
    assert vs.hash() == want        # computed, or answered by the memo
    assert vs._hash_memo[0] is vs.validators and vs._hash_memo[1] == want
    assert vs.hash() == want        # answered by the memo


def _hashed_set(n=9):
    vs = ValidatorSet([_val(i, 10 + i % 3) for i in range(n)])
    _assert_root(vs)
    return vs


def _changed(vs, changes):
    """update_with_change_set drops the memo and the root moves."""
    before = vs.hash()
    vs.update_with_change_set(changes)
    assert vs._memoised_root() is None
    assert _from_scratch(vs) != before


def _hash_mid_update(vs, changes):
    """What the explicit drop after the in-place sort is for: a hash
    between _apply_updates and the sort memoises a root on the fresh
    list in its unsorted order."""
    shift = vs._shift_by_avg_proposer_priority

    def shift_after_a_hash():
        vs.hash()
        shift()

    vs._shift_by_avg_proposer_priority = shift_after_a_hash
    try:
        _changed(vs, changes)
    finally:
        del vs._shift_by_avg_proposer_priority


def _fresh_key(rng):
    return 1000 + rng.randrange(10**6)


def _copy_then_update_the_copy(vs, rng):
    c = vs.copy()
    memo, root = vs._hash_memo, vs.hash()
    _changed(c, [Validator.new(c.validators[0].pub_key, 0),
                 _val(_fresh_key(rng), 7)])
    # the original's memo and root are untouched
    assert vs._hash_memo is memo and vs.hash() == root == _from_scratch(vs)
    return c


# each gives the set to look at next, or changes `vs` in place and gives None
STEPS = {
    "construction": lambda vs, rng: ValidatorSet(
        [_val(_fresh_key(rng), 1 + rng.randrange(50)) for _ in range(7)]),
    "copy": lambda vs, rng: vs.copy(),
    "copy_of_an_unhashed_set": lambda vs, rng: ValidatorSet(
        [v.copy() for v in vs.validators]).copy(),
    "copy_increment_proposer_priority":
        lambda vs, rng: vs.copy_increment_proposer_priority(
            1 + rng.randrange(4)),
    "increment_proposer_priority":
        lambda vs, rng: [vs.increment_proposer_priority(1 + rng.randrange(3))
                         for _ in range(4)] and None,
    "addition": lambda vs, rng: _changed(
        vs, [_val(_fresh_key(rng), 5 + 20 * k) for k in range(2)]),
    # moves the last validator to the front: every leaf's position shifts
    "power_change": lambda vs, rng: _changed(
        vs, [Validator.new(vs.validators[-1].pub_key,
                           vs.validators[0].voting_power + 1000)]),
    "removal": lambda vs, rng: _changed(
        vs, [Validator.new(vs.validators[rng.randrange(vs.size())].pub_key,
                           0)]) if vs.size() > 3 else None,
    "all_three_at_once": lambda vs, rng: _changed(
        vs, [_val(_fresh_key(rng), 11),
             Validator.new(vs.validators[-1].pub_key,
                           vs.validators[0].voting_power + 1000),
             Validator.new(vs.validators[1].pub_key, 0)])
        if vs.size() > 3 else None,
    "hash_between_merge_and_sort": lambda vs, rng: _hash_mid_update(
        vs, [Validator.new(vs.validators[-1].pub_key,
                           vs.validators[0].voting_power + 1000),
             _val(_fresh_key(rng), 1)]),
    "copy_then_update_of_the_copy": _copy_then_update_the_copy,
    "from_proto": lambda vs, rng: ValidatorSet.from_proto(vs.proto()),
    "safe_codec": lambda vs, rng: safe_codec.loads(safe_codec.dumps(vs)),
}
# a set that arrives as bytes brings no root with it
DECODED = {"from_proto", "safe_codec"}


def _step(name, vs, rng):
    root_before = vs.hash()
    got = STEPS[name](vs, rng)
    if got is None:
        got = vs
    elif name != "copy_then_update_of_the_copy":
        # the source set answers as it did
        assert vs.hash() == root_before == _from_scratch(vs)
    if name in DECODED:
        assert got._hash_memo is None and "_hash_memo" not in got.__dict__
    if name in ("copy", "copy_increment_proposer_priority"):
        # the copy hashes nothing: the root came with it, on its own list
        assert got._hash_memo == (got.validators, root_before)
        assert got._hash_memo[0] is got.validators is not vs.validators
    _assert_root(got)
    return got


@pytest.mark.parametrize("name", sorted(STEPS))
def test_memoised_root_is_the_recomputed_root_after(name):
    _step(name, _hashed_set(), random.Random(28))


@pytest.mark.parametrize("seed", range(12))
def test_memoised_root_survives_a_random_walk(seed):
    rng = random.Random(2800 + seed)
    sets = [_hashed_set(5 + seed % 4)]
    walk = []
    for _ in range(14):
        name = rng.choice(sorted(STEPS))
        walk.append(name)
        got = _step(name, rng.choice(sets), rng)
        if not any(got is s for s in sets):
            sets.append(got)
        for s in sets:      # no step on one set moved another's root
            assert s.hash() == _from_scratch(s), walk


def test_a_second_hash_computes_no_leaf(monkeypatch):
    vs = ValidatorSet([_val(i, 10) for i in range(6)])
    calls = {"tree": 0, "leaf": 0}
    tree, leaf = merkle.hash_from_byte_slices, Validator.bytes

    def counted_tree(items):
        calls["tree"] += 1
        return tree(items)

    def counted_leaf(self):
        calls["leaf"] += 1
        return leaf(self)

    monkeypatch.setattr(merkle, "hash_from_byte_slices", counted_tree)
    monkeypatch.setattr(Validator, "bytes", counted_leaf)
    first = vs.hash()
    assert calls == {"tree": 1, "leaf": 6}
    assert vs.hash() == first
    assert vs.copy().hash() == first
    assert vs.copy_increment_proposer_priority(2).hash() == first
    assert calls == {"tree": 1, "leaf": 6}
    # a set of the same validators that never met the first computes
    assert ValidatorSet.from_proto(vs.proto()).hash() == first
    assert calls == {"tree": 2, "leaf": 12}


def test_no_codec_carries_the_root():
    vs = _hashed_set(4)
    assert "_hash_memo" in vs.__dict__
    assert "_hash_memo" not in vs.__getstate__()
    for back in (pickle.loads(pickle.dumps(vs)),
                 safe_codec.loads(safe_codec.dumps(vs)),
                 ValidatorSet.from_proto(vs.proto())):
        assert "_hash_memo" not in back.__dict__
        assert back._hash_memo is None
        assert back.hash() == vs.hash() == _from_scratch(back)

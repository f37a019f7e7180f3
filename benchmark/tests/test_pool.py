"""The bulk pool's triples: made from the seed, and each made with the
verdict the plain reference gives it.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import hashlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import pool  # noqa: E402
from benchmark.reference import bls  # noqa: E402
from benchmark.reference.bls12_381 import curves as c  # noqa: E402
from benchmark.reference.bls12_381 import hash_to_curve as h2c  # noqa: E402
from benchmark.reference.bls12_381.constants import R  # noqa: E402


def test_fast_hash_gives_the_reference_point():
    for i in range(4):
        msg = hashlib.sha256(b"m%d" % i).digest()
        assert pool.hash_to_g2(msg) == h2c.hash_to_g2(msg)


def test_order13_point_is_on_the_curve_outside_g2():
    t = pool.order13_point()
    assert t is not None and c.g2_is_on_curve(t)
    assert c.g2_mul(t, 13) is None
    assert not c.g2_in_subgroup(t)


def test_pool_is_made_from_the_seed_with_the_reference_verdicts():
    seed = 2**31 + 11
    made = pool.make(seed, 4, 2, {"wrong_message": 1, "subgroup": 1},
                     workers=2)
    again = pool.make(seed, 4, 2, {"wrong_message": 1, "subgroup": 1},
                      workers=2)
    other = pool.make(seed + 1, 4, 2, {"wrong_message": 1, "subgroup": 1},
                      workers=2)
    assert made["triples"] == again["triples"]
    msgs = [t[1] for t in made["triples"]]
    assert len(set(msgs)) == len(msgs)
    assert not set(msgs) & {t[1] for t in other["triples"]}
    assert [len(s) for s in made["slices"]] == [4, 4] and \
        len(made["warm"]) == 4
    picks = [made["slices"][0][0], made["warm"][0],
             *made["invalid"]["wrong_message"], *made["invalid"]["subgroup"]]
    for i in picks:
        pk, msg, sig, valid = made["triples"][i]
        assert bls.verify(pk, msg, sig) == valid
    _, _, sig, _ = made["triples"][made["invalid"]["subgroup"][0]]
    assert c.g2_is_on_curve(sig) and c.g2_mul(sig, R) is not None

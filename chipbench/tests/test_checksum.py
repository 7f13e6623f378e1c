"""The host's checksums, summed in blocks on threads, give the numbers of
one pass over each leaf and of the device's checksums, and see an element
changed or two swapped."""
import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from chipbench import checksum


def _one_pass(tree) -> list:
    out = []
    for leaf in jax.tree.leaves(tree):
        a = np.ascontiguousarray(np.asarray(leaf)).reshape(-1)
        w = a.view(np.uint32) if a.dtype.itemsize == 4 \
            else a.view(np.uint16).astype(np.uint32)
        pos = np.arange(w.size, dtype=np.uint32) * np.uint32(checksum.KNUTH) \
            + np.uint32(1)
        out.append(np.array([np.sum(w, dtype=np.uint32),
                             np.sum(w * pos, dtype=np.uint32)], np.uint32))
    return out


def _tree():
    rng = np.random.default_rng(3)
    return {"w": rng.standard_normal((37, 29), np.float32),
            "b": rng.standard_normal(101).astype(ml_dtypes.bfloat16),
            "empty": np.zeros((0, 4), np.float32),
            "wt": np.asfortranarray(rng.standard_normal((13, 11), np.float32))}


@pytest.mark.parametrize("block", [7, 64, 1 << 24])
def test_blocks_give_the_one_pass_sums(monkeypatch, block):
    monkeypatch.setattr(checksum, "BLOCK", block)
    tree = _tree()
    got = checksum.host_checksums(tree)
    np.testing.assert_array_equal(got, _one_pass(tree))
    dev = jax.jit(checksum.device_checksums)(
        jax.tree.map(jnp.asarray, tree))
    np.testing.assert_array_equal(got, [np.asarray(d) for d in dev])


def test_changed_or_moved_elements_are_seen(monkeypatch):
    monkeypatch.setattr(checksum, "BLOCK", 7)
    tree = _tree()
    want = checksum.host_checksums(tree)
    changed = dict(tree, w=tree["w"].copy())
    changed["w"][3, 4] = np.nextafter(changed["w"][3, 4], np.float32(9))
    swapped = dict(tree, w=tree["w"].copy())
    swapped["w"][[0, 36]] = swapped["w"][[36, 0]]
    w = sorted(tree).index("w")     # leaves come in the keys' order
    for bad in (changed, swapped):
        got = checksum.host_checksums(bad)
        assert not np.array_equal(got[w], want[w])
        assert all(np.array_equal(got[i], want[i])
                   for i in range(len(want)) if i != w)

import jax
import jax.numpy as jnp
import numpy as np

from glt_tpu.ops import ordered_unique


def test_ordered_unique_first_occurrence():
  ids = jnp.array([7, 3, 7, 9, 3, 1])
  valid = jnp.ones(6, bool)
  uniq, count, inv = ordered_unique(ids, valid, capacity=8)
  assert int(count) == 4
  np.testing.assert_array_equal(np.asarray(uniq),
                                [7, 3, 9, 1, -1, -1, -1, -1])
  np.testing.assert_array_equal(np.asarray(inv), [0, 1, 0, 2, 1, 3])


def test_ordered_unique_with_invalid():
  ids = jnp.array([5, 5, 2, 8, 2])
  valid = jnp.array([True, False, True, False, True])
  uniq, count, inv = ordered_unique(ids, valid, capacity=4)
  assert int(count) == 2
  np.testing.assert_array_equal(np.asarray(uniq)[:2], [5, 2])
  np.testing.assert_array_equal(np.asarray(inv), [0, -1, 1, -1, 1])


def test_ordered_unique_all_invalid():
  ids = jnp.array([1, 2, 3])
  valid = jnp.zeros(3, bool)
  uniq, count, inv = ordered_unique(ids, valid, capacity=4)
  assert int(count) == 0
  assert np.all(np.asarray(uniq) == -1)
  assert np.all(np.asarray(inv) == -1)


def test_ordered_unique_jit_and_big_random():
  rng = np.random.default_rng(0)
  ids = rng.integers(0, 50, size=257)
  fn = jax.jit(lambda x: ordered_unique(x, jnp.ones(257, bool), 257))
  uniq, count, inv = fn(jnp.asarray(ids))
  # numpy reference: first-occurrence order
  _, first_idx = np.unique(ids, return_index=True)
  expect = ids[np.sort(first_idx)]
  assert int(count) == len(expect)
  np.testing.assert_array_equal(np.asarray(uniq)[:len(expect)], expect)
  # inverse maps back to original values
  np.testing.assert_array_equal(np.asarray(uniq)[np.asarray(inv)], ids)


def test_stitch_rows_pad_does_not_clobber_row_zero():
  from glt_tpu.ops import stitch_rows
  # partition A serves positions [0, -1(pad)]; B serves [1]
  out = stitch_rows(
      [jnp.array([0, -1]), jnp.array([1])],
      [jnp.array([[42.], [99.]]), jnp.array([[7.]])],
      total=2)
  np.testing.assert_allclose(np.asarray(out), [[42.], [7.]])

"""Ahead-of-time compiles of the MwCAS kernel path for a described TPU v5e.

Nothing runs: the TPU compiler that ships with jaxlib compiles for a chip
that is described, not attached, and refuses what Mosaic would refuse on
the chip (block tiling, dtypes, VMEM).  Interpret mode accepts all of
that, so these tests are the only CPU-side guard that the served path's
kernel still lowers.  Each asserts the kernel is really in the program
(``tpu_custom_call``).

The topology is described inside fixtures of this one file, never while a
module is imported: only one process may load the TPU library, and under
several test workers only the worker that runs this file may try.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.pmwcas import (pmwcas_apply_stacked, pmwcas_success_pallas,
                          reserve_slots)

R = 1024            # the service round cap the chip smoke runs at


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(one_chip, fn, *shapes, **static):
    """Compile ``fn(*shapes, interpret=False, **static)`` for one chip."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    fn = jax.jit(functools.partial(fn, interpret=False, **static))
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("B,K", [(16, 2), (R, 2), (R, 8)])
def test_success_kernel_compiles(one_chip, B, K):
    text = _compiled_text(one_chip, pmwcas_success_pallas,
                          ((B, K), jnp.int32), ((B, K), jnp.uint32),
                          ((B, K), jnp.uint32))
    assert "tpu_custom_call" in text


def test_stacked_dispatch_compiles(one_chip):
    S, W, B, K = 4, 2 ** 20, R, 4
    text = _compiled_text(one_chip, pmwcas_apply_stacked,
                          ((S, W), jnp.uint32), ((S, B, K), jnp.int32),
                          ((S, B, K), jnp.uint32), ((S, B, K), jnp.uint32),
                          use_kernel=True)
    assert "tpu_custom_call" in text


def test_lone_shard_stacked_dispatch_compiles(one_chip):
    """A 1-shard kernel deployment takes the same stacked dispatch at
    S = 1, B = round_cap."""
    S, W, B, K = 1, 2 ** 21, R, 2
    text = _compiled_text(one_chip, pmwcas_apply_stacked,
                          ((S, W), jnp.uint32), ((S, B, K), jnp.int32),
                          ((S, B, K), jnp.uint32), ((S, B, K), jnp.uint32),
                          use_kernel=True)
    assert "tpu_custom_call" in text


# B=64 K=4 is a serving-layer batch; the rest are the free-list shapes a
# BzTree issues on a kernel backend: alloc([1]) and free() of one slot
# (1,1), free() of a grant (1,n), a multi-request alloc (n,n), and the
# recovery reserve of one slot per request (n,1), below and above a tile
@pytest.mark.parametrize("W,B,K", [(256, 64, 4), (37, 1, 1), (37, 1, 3),
                                   (37, 3, 3), (37, 13, 1), (256, 200, 1)])
def test_reserve_slots_compiles(one_chip, W, B, K):
    text = _compiled_text(one_chip, reserve_slots, ((W,), jnp.uint32),
                          ((B, K), jnp.int32))
    assert "tpu_custom_call" in text

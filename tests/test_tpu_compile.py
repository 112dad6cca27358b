"""The three Hausdorff kernels compile for a TPU v5e at the widths the chip
smoke runs them.

Interpret mode checks none of the TPU compiler's rules (block tiling, fast
memory), so these compiles are the only CPU-side guard that the kernels
still build for the chip.  The topology is described in a fixture, never
at import: only one process may hold the TPU library, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.hausdorff import batched, ops

D = 128         # late-interaction token width; the pairwise D=64 pads to it
BLOCK = 512     # what resolve_block_sizes hands every TPU kernel
N_PAIR = 50_000
N_Q = 32        # a ColBERT query
SETS = 32_768   # pow2 frontier of the smoke's largest bucket
Q_BATCH = 8
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    # without it the TPU compiler writes its logs under the temp directory
    had_log_dir = "TPU_LOG_DIR" in os.environ
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        if not had_log_dir:
            os.environ.pop("TPU_LOG_DIR", None)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    return compiled.as_text(), used


def test_fused_kernel_compiles(one_chip):
    def fn(a, b):
        return ops.fused_min_sqdists(a, b, block_a=BLOCK, block_b=BLOCK, interpret=False)

    f32 = jnp.float32
    text, used = _compile(fn, one_chip, ((N_PAIR, 64), f32), ((N_PAIR, 64), f32))
    assert "tpu_custom_call" in text
    assert used < HBM_BYTES


@pytest.mark.parametrize("cap", [64, 128, 256])
def test_batched_kernel_compiles(one_chip, cap):
    def fn(q, slab, valid):
        return batched.batched_min_sqdists(
            q, slab, valid_slab=valid, block_a=BLOCK, block_b=BLOCK, interpret=False
        )

    sets = SETS * 128 // cap
    text, used = _compile(
        fn, one_chip, ((N_Q, D), jnp.float32), ((sets, cap, D), jnp.float32),
        ((sets, cap), jnp.bool_),
    )
    assert "tpu_custom_call" in text
    assert used < HBM_BYTES


@pytest.mark.parametrize("cap", [64, 128, 256])
def test_multiquery_kernel_compiles(one_chip, cap):
    def fn(qs, slab, valid):
        return batched.multiquery_min_sqdists(
            qs, slab, valid_slab=valid, block_a=BLOCK, block_b=BLOCK, interpret=False
        )

    sets = SETS * 128 // cap
    text, used = _compile(
        fn, one_chip, ((Q_BATCH, N_Q, D), jnp.float32),
        ((sets, cap, D), jnp.float32), ((sets, cap), jnp.bool_),
    )
    assert "tpu_custom_call" in text
    assert used < HBM_BYTES


def test_lower_bound_ops_keep_their_scope(one_chip):
    """ProHD's certified lower bound compiles for the chip to one sort and
    elementwise passes, and every op the chip runs for it carries
    ``prohd.lower_bound``, which ``lower_bound_ms`` reads.  Left out: the
    compiler's own memory-space copies, which carry no ``op_name``."""
    import re

    from repro.core.projected import projected_hd
    from repro.core.prohd import LOWER_BOUND
    from repro.obs import xla

    def fn(proj_a, proj_b):
        with jax.named_scope(LOWER_BOUND):
            return projected_hd(proj_a, proj_b)

    n = 8192
    text, _ = _compile(fn, one_chip, ((n, 17), jnp.float32), ((n, 17), jnp.float32))
    entry = text[text.index("\nENTRY"):]
    ops = dict(re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? ([\w\-]+)\(", entry, re.M))
    free = {"parameter", "constant", "bitcast", "get-tuple-element", "tuple",
            "copy-start", "copy-done"}
    run = [name for name, opcode in ops.items() if opcode not in free]
    assert "sort" in ops.values()
    assert not {"while", "gather"} & set(ops.values())
    scopes = xla.scopes_from_hlo(text, ("prohd.",))
    assert {scopes.get(name) for name in run} == {LOWER_BOUND}

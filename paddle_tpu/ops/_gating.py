"""Single source of truth for "may this op take its Pallas path?".

Single-chip: kernels run directly (`pallas_backend_ok`).  Under a mesh
the GSPMD partitioner owns most ops, but attention composes with the
mesh through an explicit shard_map (flash_attention_spmd) — gate that
with `pallas_tpu_ok`, which drops the no-mesh condition.

PADDLE_TPU_PALLAS_INTERPRET=1 runs every kernel in Pallas interpret
mode (pure Python, any backend) — correctness testing on the CPU mesh.
It is a CPU test switch only: on the chip a kernel either compiles
through Mosaic or its gate sends the shape to XLA (chip_smoke.py
refuses to run with the variable set).
"""
import os

import jax

INTERPRET = os.environ.get('PADDLE_TPU_PALLAS_INTERPRET') == '1'

# A row-wise kernel (LayerNorm, softmax) holds whole rows in one block.
# Mosaic double-buffers each in/out block and the body keeps a couple
# of f32 copies live, so the f32 image of ONE block is capped at 1 MiB:
# ~6 such buffers stay well inside the 16 MiB scoped-VMEM default.  A
# row too long for that (softmax over a 50k vocabulary) goes to XLA.
ROW_BLOCK_F32_BYTES = 1 << 20


def pallas_tpu_ok():
    """Pallas kernels may run (mesh or not)."""
    return jax.default_backend() == 'tpu' or INTERPRET


def pallas_backend_ok():
    from ..distributed import env as _env
    return pallas_tpu_ok() and _env.get_mesh() is None


def pick_block_rows(n_rows, block_rows, row_len):
    """Rows per block for a row-wise kernel over [n_rows, row_len]:
    the largest power-of-two divisor of n_rows up to block_rows whose
    block fits ROW_BLOCK_F32_BYTES, or None when no block of at least
    8 rows exists (the caller goes to XLA)."""
    cap = ROW_BLOCK_F32_BYTES // (4 * max(row_len, 1))
    br = block_rows
    while br > 1 and (br > cap or n_rows % br != 0):
        br //= 2
    return br if (n_rows % br == 0 and 8 <= br <= cap) else None

# Pallas TPU kernels for the Scatter-Combine hot paths:
#   segment_combine  — the paper's active-message combine (⊕ over dst-sorted
#                      edges) as block-local one-hot reductions;
#   flash_attention  — blocked online-softmax attention for the LM archs.
# ops.py holds the jit'd wrappers; ref.py the pure-jnp oracles.
import functools

import jax


def on_backend(kernel, *args):
    """Run `kernel(*args, interpret=...)` as the backend being lowered for
    requires: Mosaic-compiled on a TPU, the Pallas interpreter on CPU.

    This is the one place interpret mode is decided.  The choice follows
    the LOWERING platform (`lax.platform_dependent`), so a program compiled
    for a described TPU from a CPU host gets the real kernel too; any other
    backend fails to lower instead of running an interpreted kernel."""
    return jax.lax.platform_dependent(
        *args, cpu=functools.partial(kernel, interpret=True),
        tpu=functools.partial(kernel, interpret=False))

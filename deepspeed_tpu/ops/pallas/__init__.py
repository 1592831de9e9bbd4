"""Pallas TPU kernels. Importing the package imports no kernel (and not
``jax.experimental.pallas``, about a second): callers import the module they
call."""

# The two residuals of an attention backward that only the forward kernel
# can produce, under the names an activation-checkpoint policy keeps them by
# (``models/common.py::remat_wrap``). ``flash_attention.py``'s forward
# rule names them; an attention no kernel ran names its output the same.
SAVED_O, SAVED_LSE = "attn_out", "attn_lse"
# What the KDA state pass's backward reads and only its forward can make: the
# state at the end of every group of chunks (``kda.py``'s forward rule names
# it; the outputs go under ``SAVED_O``).
SAVED_KDA_STATES = "kda_states"

"""Pallas kernels of the GNN hot path (``neighbor_agg``) and the LM
stack (``flash_attn``).

Interpret mode follows the backend: Mosaic compiles the kernels on a
TPU, and every other backend runs them in the Pallas interpreter.  No
config or caller picks it, so a chip run cannot silently interpret."""
import jax


def default_interpret() -> bool:
    """True on any backend but a TPU."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret) -> bool:
    """A kernel's ``interpret`` argument: None follows the backend; an
    explicit True on a TPU backend raises (a TPU never interprets)."""
    if interpret is None:
        return default_interpret()
    if interpret and not default_interpret():
        raise ValueError("Pallas interpret mode was requested on a TPU "
                         "backend; the kernels compile there")
    return bool(interpret)

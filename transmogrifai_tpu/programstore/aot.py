"""jax.export glue: serialize a jitted program, rebuild a callable.

The only module in the package that touches jax. ``export_bytes`` traces
the jitted function once against the call's concrete arguments (shapes +
dtypes become the exported avals — exactly the shapes the padded dispatch
sites replay) and serializes the StableHLO artifact;
``load_callable`` deserializes and wraps the exported module in one thin
``jax.jit`` so repeated dispatches reuse the compiled executable instead
of re-staging the module per call.

What the round trip buys: a fresh process skips the Python trace of the
whole stage chain (the dominant cold-start cost at this repo's scale —
dozens of ``device_columnar`` stages per segment, plus the zero-row
probe-and-partition pass that ``plan.get_plan`` pays per schema). XLA
still compiles the deserialized StableHLO on first call; layered under
``utils/jax_cache.py``'s persistent XLA cache that compile is itself a
disk hit for unchanged modules. Outputs are bit-identical to the freshly
traced program — same StableHLO, same compiler, same device — which is
why the store keys on (jaxlib version × device kind) and refuses to
cross either boundary (docs/serving.md "AOT cold start & the program
store").
"""
from __future__ import annotations

from typing import Any, Callable, Tuple


def current_jaxlib() -> str:
    import jaxlib
    return str(jaxlib.__version__)


def current_device_kind() -> str:
    """``<platform>/<device_kind>`` of the first local device — one half
    of the store key: an artifact exported for one backend must never
    deserialize onto another."""
    import jax
    d = jax.local_devices()[0]
    return f"{d.platform}/{d.device_kind}"


def export_bytes(jitted_fn: Callable, args: Tuple[Any, ...]) -> bytes:
    """Serialize ``jitted_fn`` lowered at ``args``' avals (concrete
    arrays or ShapeDtypeStructs both work — export reads shapes/dtypes,
    never values)."""
    from jax import export as jexport
    return bytes(jexport.export(jitted_fn)(*args).serialize())


def load_callable(blob: bytes) -> Callable:
    """Deserialize an exported program into a dispatchable callable.
    Raises on any malformed/incompatible blob — the store turns that
    into a typed fallback, never an error on a request path."""
    import jax
    from jax import export as jexport
    exported = jexport.deserialize(bytearray(blob))
    return jax.jit(exported.call)

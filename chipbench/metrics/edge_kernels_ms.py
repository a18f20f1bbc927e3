"""Edge-list kernels: device time per traced interval of the stitched
sparse Pallas kernels, read from the breakdown's ``device_ops``.

The kernels carry their names on the op in the chip's device trace, one
op per call site: ``edge_flow_step.N`` (``kernels/flow_step_sparse.py``)
and ``edge_omd_update.N`` (``kernels/omd_update_sparse.py``).  The XLA
gather of the in-edge shares ``pv`` that feeds the flow kernel is not
counted: XLA names its fusion ``fusion.N`` whatever scope it runs under,
and the reduced trace keeps the op's name only.  ``device_ops`` holds
the ten longest ops, so a call site outside them is not counted either.
None where no op of these names is there (the jnp path, or no trace).
"""

KERNELS = ("edge_flow_step", "edge_omd_update")


def kernel_seconds(trace) -> float | None:
    """Seconds of the traced window in ops named after the kernels."""
    if trace is None:
        return None
    hits = [s for name, s in trace["device_ops"]
            if name.rsplit(".", 1)[0] in KERNELS]
    return sum(hits) if hits else None


def read(ctx: dict):
    seconds = kernel_seconds(ctx["trace"])
    if seconds is None:
        return None
    return 1e3 * seconds / ctx["trace"]["n_intervals"]

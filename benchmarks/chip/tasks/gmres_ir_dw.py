"""The program's GMRES-IR task on the double-word f32 carrier: its fp64
steps carry 48 significand bits on a chip that has no f64."""
INNER_METRIC = "n_gmres"


def build(config: dict, backend=None, executor=None):
    from repro.precision import DoubleWord, resolve_backend
    from repro.solvers.ir import IRConfig
    from repro.tasks import GMRESIRTask
    b = config["batcher"]
    return GMRESIRTask(ir_cfg=IRConfig(**config["solver"]),
                       bucket_step=b["bucket_step"],
                       min_bucket=b["min_bucket"],
                       backend=DoubleWord(resolve_backend(backend)),
                       executor=executor)

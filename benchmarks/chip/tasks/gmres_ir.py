"""The program's GMRES-IR task, built from a configuration."""
INNER_METRIC = "n_gmres"


def build(config: dict, backend=None, executor=None):
    from repro.solvers.ir import IRConfig
    from repro.tasks import GMRESIRTask
    b = config["batcher"]
    return GMRESIRTask(ir_cfg=IRConfig(**config["solver"]),
                       bucket_step=b["bucket_step"],
                       min_bucket=b["min_bucket"],
                       backend=backend, executor=executor)

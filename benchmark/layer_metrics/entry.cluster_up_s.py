"""entry / head: seconds ray_tpu.init() took in the driver. Moves setup_s."""


def read(run):
    return run.driver["cluster_up_s"]

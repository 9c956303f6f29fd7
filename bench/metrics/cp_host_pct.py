"""Share of the window the control plane's mapping events held the host:
the growth of the engine's ``mapping_wall_s`` counter, which already holds
the pruner's ``pruning_wall_s``, over the window's length."""


def read(run):
    d = run["s1"]["mapping_wall_s"] - run["s0"]["mapping_wall_s"]
    return 100.0 * d / run["seconds"]

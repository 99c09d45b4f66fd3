"""Where the benchmark's leaves sit in the program's parameter trees:
the one place both placements take a DLRM tower's paths from."""


def tower_paths(config, prefix=()):
    """weights leaf name -> path of the tower's leaf under ``prefix``."""
    paths = {}
    for mlp, name, widths in (("MLP_0", "bottom", config["bottom_mlp"]),
                              ("MLP_1", "top", config["top_mlp"])):
        for i in range(len(widths)):
            for leaf in ("kernel", "bias"):
                paths[f"{name}.{i}.{leaf}"] = (*prefix, mlp, f"Dense_{i}",
                                               leaf)
    return paths


def get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def put(tree, path, value):
    get(tree, path[:-1])[path[-1]] = value

"""What a configuration's ``kind`` brings, found by name like everything else:
``kinds/<kind>.py`` beside the configurations, loaded by path. A kind file
says how to turn the published keys into the program's config and where its
init, loss, sharding rules, operation count and plain reference are, as
module-level names (the ``HOOKS``). A new architecture is a new kind file with
a reference and counts of its own; nothing here knows one."""

from __future__ import annotations

import glob
import os

from benchmarks.chip import harness

# what the runners ask a kind for; a kind that only trains or only serves
# leaves the other runner's hooks out
HOOKS = {
    "program_config": "both runners: (published keys, n_layers=, max_seq_len=) -> the program's config",
    "init": "both runners: (program config, key) -> the parameter tree",
    "loss": "train: (program config, **loss_kwargs) -> fn(params, batch)",
    "shard_rules": "train: () -> the sharding rules `Accelerator.prepare` takes",
    "forward_flops_per_token": "both runners: (published keys, seq_len, n_layers) -> matmul operations",
    "reference_loss": "train: (published keys) -> fn(params, batch), the plain float32 loss",
    "reference_logits": "serve: (published keys) -> fn(params, ids [T]) -> float32 logits [T, vocab]",
}


class Kind(dict):
    """The hooks one kind file exports. Asking for one it lacks says which
    file lacks what, and what the hook is."""

    def __init__(self, path: str, module):
        super().__init__({h: getattr(module, h) for h in HOOKS if hasattr(module, h)})
        self.path, self.module = path, module

    def __missing__(self, hook):
        raise KeyError(f"{self.path} has no `{hook}` ({HOOKS.get(hook, 'not a hook')}); "
                       f"it has {sorted(self)}")


def kind_of(config: dict, root: str = harness.HERE) -> Kind:
    """The kind file of a configuration, from ``<root>/kinds/``: ``root`` is
    the directory the cell's other files were found in (``Cell.root``)."""
    name = config["kind"]
    path = os.path.join(root, "kinds", name + ".py")
    if not os.path.exists(path):
        have = sorted(os.path.basename(p)[:-3] for p in glob.glob(os.path.join(root, "kinds", "*.py")))
        raise KeyError(f"no model kind {name!r}: no {path}; the kinds there are {have}")
    return Kind(path, harness.module_from_path(path, "chip_benchmark_kind_"))


def depth(cell) -> int:
    """Depth is the one key a cell may set over its configuration."""
    return int(cell.spec.get("n_layers", cell.config["num_hidden_layers"]))

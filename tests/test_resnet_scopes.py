"""Every ResNet plan layer's ops carry the layer's name in the compiled
step's HLO `op_name` (models.cnn.resnet runs each under
core.trace.layer_context), so a device trace can tell the layers apart."""
import re

import jax

from repro.launch import train
from repro.launch.mesh import make_mesh
from repro.models.cnn import resnet


def scopes(cfg):
    """The plan layers of `resnet_graph`, each block's residual sum
    `res{s}{b}`, the global average pool and the head."""
    names = set(resnet.resnet_graph(1, cfg).nodes) | {"pool5", "fc"}
    return names | {n.split("_")[0] for n in names if "_branch" in n}


def test_the_compiled_step_names_every_layer():
    args = train.parser().parse_args(
        ["--arch", "resnet50", "--smoke", "--batch", "2", "--steps", "3"])
    mesh = make_mesh(data=1, model=1)
    cfg, params, opt, loss, mk, put, prec, _ = train.build(args, mesh)
    state = train.train_state(params, opt, mesh)
    step = train.train_step(args, opt, loss, prec, mesh, state)
    text = step.lower(*state, put(mk(0))).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', text)
    want = scopes(cfg)
    assert len(want) == 2 + 4 * 4 + 4 + 2      # smoke: 1 block a stage
    seen = {n for n in want
            if any(re.search(rf"(^|[/(]){n}([/)]|$)", o) for o in op_names)}
    assert seen == want
    # the backward pass is named too
    assert any(re.search(r"transpose\(jvp\(res5a_branch2b\)\)", o)
               for o in op_names)
    # the max pool's own scope (core.spatial_conv.spatial_pool), on its
    # forward and its backward ops, inside pool1 alone
    pool = [o for o in op_names if "/max_pool/" in o]
    assert any(o.startswith("jit(step)/jvp(pool1)/") for o in pool)
    assert any(o.startswith("jit(step)/transpose(jvp(pool1))/")
               for o in pool)
    assert all("pool1" in o for o in pool)

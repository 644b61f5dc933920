"""Plain float32 references the checks compare the program with.

A configuration names its reference in `reference.module`; the harness
loads `bench/reference/<module>.py` (`bench.harness.load_reference`) and
hands it to the job, which calls it and no other. Such a module imports
nothing of the program and takes nothing the program has made. With
`model` the configuration's own `model` dict, as it stands in the file,
and `prec` either "f32" or "fp8" (the control: every matrix product's
operands in float8), it offers:

* `init_params(key, shapes, dtype, **config["weights"])`: weights in the
  program's layout `shapes`, from one key (jitted: made on the device);
* `hidden(model, params, tokens, prec)`: final normed hidden states
  [B, S, d] of token ids [B, S];
* `logits(model, params, h, prec)`: the logits of hidden states;
* for training, `loss_and_grad(model, params, tokens, targets, micro,
  prec)`: the mean loss and the float32 gradient over a batch, taken in
  blocks of `micro` rows.

`transformer` is the repo's decoder stack. `serve` (the replay of served
requests, which also reads the "margin", best less second-best "f32"
logit) and `swarm` (SwarmSGD's first supersteps) drive whichever module
they are given.
"""
import json


def model_key(model: dict) -> str:
    """A hashable, exact form of a `model` dict with nested values, for
    caches and static jit arguments; `json.loads` gives the dict back."""
    return json.dumps(model, sort_keys=True)

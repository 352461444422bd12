"""One module per model family: what the benchmark knows of an architecture.

A configuration file names its family with ``"family": "<module>"``; a file
without the key is ``decoder``. A family module provides:

* ``KEYS``: published key name -> the program's ``ModelConfig`` field;
* ``PROGRAM_ONLY_KEYS``: keys the program takes and the reference does not;
* ``model(config)``: the reference's sizes, a frozen dataclass that carries
  ``param_dtype`` (hashable: ``init_params`` is jitted with it static);
* ``init_params(model, key)``: the program's tree, by its key schedule;
* ``row_loss(params, model, tokens, labels, precision)``: the loss of one
  batch row in float32, a mean over the row's tokens, with the control's
  rounding from ``reference._matmul`` / ``reference._activation``;
* ``forward_flops_per_token(config, seq)`` and ``param_count(config)``: the
  benchmark's own counts, from the file's keys, never from the program.

A new family is a new module here and a key in its configuration file.
"""

import importlib


def load(config: dict):
    """The family module that the configuration file names."""
    return importlib.import_module(f"{__name__}.{config.get('family', 'decoder')}")

"""Plain float32 references, one module per architecture.

A configuration names its reference by path: the ``reference`` key of its
file under ``bench/configs/`` (beside the ``model`` section the program
runs).  The app loads that file as a module, so a new architecture brings a
new file and no edit of the harness.  A reference imports nothing of the
program and takes nothing the program made; its weights come from
``bench.weights`` and the run's key, as the program's did.

A module used by a serving cell exposes

    Reference(model, *, weight_dtype, precision) -> object with
        forward(key, tokens, read) -> logits (R, len(read), V)

where ``model`` is the configuration's ``model`` section, ``weight_dtype``
the dtype the weights are served in, ``precision`` ``"reference"`` or
``"control"`` (the lower-precision control), ``key`` the run's weight key,
``tokens`` an ``(R, T)`` array and ``read`` the slice of positions whose
logits are returned.

A module used by a training cell exposes

    first_steps(model, key, batches, optimizer, devices, precision) -> dict
        {"losses": [...], "grad1": {leaf: norm}, "change3": {leaf: norm}}

the steps' losses, each leaf's norm of the first clipped gradient and of the
weights' change over all the steps (``"<layer>:<name>"`` for a layer's
leaf), with ``optimizer`` the traffic file's ``AdamWConfig`` fields.
"""

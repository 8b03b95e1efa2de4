"""One module per layer kind: what the benchmark knows of an architecture.

A configuration's ``layer_types`` names each kind, and ``loader.layer(kind)``
finds ``bench/layers/<kind>.py`` (an unknown kind stops the run before any
weights are drawn).  A configuration of a new architecture joins the
benchmark by adding its config file and one module for each new kind; no
other file changes.  Every module gives:

``reference(p, x, cfg, precision) -> x``
    The float32 bidirectional layer over ``x`` (B, S, d), from the
    layer's parameters ``p`` (one slice of the stacked segment
    ``backbone/segs/<i>_<kind>``).  Written from the published description,
    with each departure noted in the module's docstring.  Matrix products
    go through ``reference.mm`` (or ``linear``), so that ``precision="fp8"``
    rounds their operands: the control.  It imports nothing of the program.

``matmul_flops(cfg, rows, seq) -> float``
    Every matrix product of one layer over ``rows`` x ``seq`` positions, 2
    flops a multiply-add, attention's score and value products over the
    (query, key) pairs its mask admits included (``bench/flops.py``).

``flash_calls(cfg, rows, seq) -> list[(flops, bytes)]``
    The flash-attention calls of one layer, each as
    ``flops.flash_attention_call`` counts it; a kind whose q/k and v head
    dims differ counts its own.  The kernel's roofline reader sums them.

``program_keys(pcfg) -> dict``
    Keys of the configuration file and the program's values for them
    (``pcfg`` is the program's ``ModelConfig``), beyond the common ones
    ``weights.program_config`` compares: the widths of this kind, so that
    no change to the program can shrink what the benchmark runs.

and may give:

``init_leaf(names, shape, key, gain) -> array``
    The draw of a parameter that no rule of ``bench/weights.py`` covers
    (``names`` is its path).  Consulted for no other leaf.
"""

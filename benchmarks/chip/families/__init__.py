"""Model families: all the harness knows of a model's shape.

A configuration file in ``configs/`` names its family under ``"family"``;
the harness imports ``families/<family>.py`` and asks it, and nothing
else, about the model. A family module provides:

* ``program_config(conf)``: the registry's ``ModelConfig``, set to the
  file's published values and checked against them;
* ``dims(conf)``: what the per-layer readers see as ``ctx.dims``, or
  ``None`` where the family's readers count their own work;
* ``vocab(conf)``: the vocabulary the traffic draws its ids from (the
  slice held here, where the vocabulary is cut);
* ``gaps(conf, seed32, prompts, served, max_new)``: for each request, the
  gap of each served token below the best logit of the family's plain
  reference, which imports nothing of the program.

A new family is new files: this module, its reference, its configs,
its traffic and its readers.
"""

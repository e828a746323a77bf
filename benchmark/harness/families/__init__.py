"""The FAMILY of a configuration: everything the harness has to know about
one architecture, found by the ``model_type`` its configuration's file
states (the source's own; ``-`` reads ``_``).  Nothing lists the families:
a later PR brings ``families/<model_type>.py``, its plain reference beside
it, and a configuration file that names it.  The drivers, the checks and the
readers ask the family and read no model key themselves.

The contract.  A family module gives, each taking the configuration's file
as read (``config``):

* the shapes: ``sizes(config)``, what shapes the model, under the keyword
  names the program's model takes (the trainer's ``model_overrides``);
  ``vocab(config)``, the ids traffic may draw from;
* the weights: ``make_weights(seed, config)``, the float32 tree in the
  layout the program reads, from the seed, in ONE jitted call; for a
  training cell also ``leaf_norms(tree)`` (one norm a leaf that the gradient
  and the change are compared on, in one call, in an order of its own
  choosing that is the same for every tree);
* the program's side: ``model(config)``, the program's own description of
  the model, the object ``ServingEngine.from_config`` takes (the one place
  under ``benchmark/`` that imports a model class of the program);
  ``compute_dtype(config)``, what ``kv_dtype: "model"`` resolves to; for a
  training cell ``takes_flash(config, seq_len, head_width)``, whether the
  program's model takes the flash kernel at that shape;
* the plain reference, which imports nothing of the program.  Serving:
  ``reply_logits(params, prompt, reply, config, max_reply, precision)``,
  ``chosen_tokens(logits)``, ``planted(params, fault, config)`` (the
  weights a planted fault computes with; the fault names are the family's
  own, ``""`` is no fault and an unknown one is a ``ValueError``) and
  ``faulty_context(fault, prompt, neighbour, chunk)``.
  Training: ``train_steps(params, batches, config, opt, precision, rows,
  fault)``, giving ``losses``, ``first_grads`` and ``params``;
* the work: ``model_flops(config, fed, sampled)``, the matrix products of
  ``fed`` tokens fed of which ``sampled`` go through the head; and
  ``attention_layers(config)``, a list of ``(layers,
  query_heads, kv_heads, head_width)``, one entry a group of softmax
  attention layers that share a shape (``[(36, 20, 20, 64)]`` for GPT-2
  large; a layer without paged or flash attention is in no entry), which the
  paged and flash rooflines and both schedule counters read.

A serving family needs no training entry and the other way round: a
function is looked up when a cell calls it.  Every entry above has a caller
in a driver, a check or a reader; what nothing generic calls (a parameter
count, the leaves by name, a list of its faults) is the family's own
business.
"""

from __future__ import annotations

import importlib
import re
from types import ModuleType
from typing import Any, Dict

from benchmark.harness.manifest import ManifestError


def of(config: Dict[str, Any]) -> ModuleType:
    """The family module of a configuration's file."""
    model_type = config.get("model_type")
    if not isinstance(model_type, str) or not re.fullmatch(
            r"[A-Za-z0-9_\-]+", model_type):
        raise ManifestError(
            f"the configuration's file states no model_type a family can "
            f"be found by: {model_type!r}")
    module = f"{__name__}.{model_type.replace('-', '_')}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as exc:
        if exc.name != module:
            raise
        raise ManifestError(
            f"model_type {model_type!r} has no family {module}") from exc

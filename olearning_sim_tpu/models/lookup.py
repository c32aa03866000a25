"""A table that is only looked up, and says so.

``nn.Embed`` read through ``__call__`` alone changes, in a local SGD step,
only the rows the step's ids name; its dense gradient is zero everywhere
else. :class:`LookupOnlyEmbed` marks the looked-up rows with a Flax
perturbation so that a trainer can differentiate with respect to *them*
instead of the table (``FedCore._masked_sgd`` does, see
``engine/fedcore.py`` ``LookupTables``). Outside such a trainer the mark is
a no-op: ``perturb`` returns its input unless the ``perturbations``
collection is passed to ``apply``, so ``evaluate``, the pipeline's mirror
of the prologue, the benchmark's reference and checkpoints see ``nn.Embed``
and its parameter tree.
"""

from __future__ import annotations

import flax.linen as nn

# Name of the perturbation, beside the table's ``embedding`` in the module's
# scope: ``<module>/lookup_rows`` marks ``<module>/embedding``.
LOOKUP_ROWS = "lookup_rows"
TABLE = "embedding"


class LookupOnlyEmbed(nn.Embed):
    """``nn.Embed`` whose table is read by ``__call__(ids)`` and nothing
    else, **``ids`` being the model's input batch itself** (the trainer
    scatters its row updates by the ids it fed the model). A model that
    reads the table any other way (a tied head's ``attend``, a slice) keeps
    ``nn.Embed``: its table gradient is dense by nature.

    Give it the name the plain module would have had (``name="Embed_0"``)
    where checkpoints and mirrors read the table by path."""

    def __call__(self, ids):
        return self.perturb(LOOKUP_ROWS, super().__call__(ids))

    def attend(self, query):
        raise TypeError(
            "LookupOnlyEmbed is lookup-only: a model that also attends over "
            "the table must use nn.Embed")

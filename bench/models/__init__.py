"""One module per model family, named by a configuration's ``model`` key.

A module defines:

* ``weight_shapes(config) -> {path: (shape, init)}``: the weight tree's
  leaves, ``path`` the ``/``-joined keys of the program's parameter tree,
  ``init`` one of ``("normal", scale)``, ``("ones",)``, ``("zeros",)``.
  Leaves under ``stack/`` are stacked on a leading axis, one entry a layer,
  as the program's scanned stack keeps them; ``correct`` compares their
  norms layer by layer.
* ``loss(config, mm, params, batch) -> scalar``: the plain float32 loss of
  one batch (``tokens``, ``labels`` with ``-1`` where nothing is scored,
  and ``prefix_embeds`` for a vision configuration).  Every matrix product
  goes through ``mm(spec, a, b)``, an einsum at the highest precision or
  the reference's lower-precision control.  Nothing here imports the
  program.

``data.py`` makes, packs and measures the weights from the shapes alone,
and ``reference.py`` runs the training recipe around the loss, so a new
model is added as a module here and a configuration that names it.
"""

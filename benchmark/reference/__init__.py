"""The plain references, and what a configuration's own pieces keep to.

A configuration's file names three files (``manifest.py::named``), each a
path under ``paths`` relative to the checkout. The defaults serve the
convolutional classifiers that ``nets.py``'s walkers express; a network they
cannot express (attention, experts, more than one step an answer) brings its
own as new files, and edits none of these.

**``weights.script``** (default ``reference/weights.py``), run by
``run.py::write_weights`` as ``python <script> <the model block as JSON>
<seed> <export directory>``, a child with ``JAX_PLATFORMS=cpu`` (the server
child needs the chip) that exits 0 once the export is whole, in the format
the program's ``--ckpt`` reads for that model. It keeps to this:

- every leaf is a function of ``(seed, leaf name)`` alone (``leaves.py``: a
  counter-based generator keyed by both), so that the check makes any leaf,
  or any block of experts, again without the tree and without the export;
- leaves are made and written one at a time, in the dtype the configuration
  serves: 4.2 G parameters then cost 8.4 GB of disk and one leaf of host
  memory, where a float32 tree is 16.8 GB of the host's 40 before anything
  copies it;
- the seed is any whole number up to a little over 2**31;
- it is told nothing but the model block: what the architecture needs to
  know stands there.

``weights.py::make`` keeps its single PCG64 stream over the whole tree (24 M
values, under a second): the numbers of the cells that stand do not move.

**``check.child``** (default ``../check.py``), run by ``run.py::check_child``
once the server has gone, so it may take the chip. On standard input one
JSON document: ``model`` (the block), ``seed``, ``limits`` (the
configuration's), ``control`` (null, or the name of a lower precision that
the child knows) and ``items``, each with ``jpeg`` (base64, the bytes that
were sent) and ``served``, exactly what the server answered for that image:
``[[index, score], ...]``, or one such list a step where
``model.answer_steps`` is over 1. As its last line of standard output one
JSON object: ``correct``, ``compared`` (each name of ``limits`` with its
``value`` and ``limit``), ``images``, ``platform`` (``check.py::answer``).
It imports nothing of the program and reads nothing that the program or the
weights script made: it makes the leaves it needs from the seed, walks the
plain float32 reference at ``highest`` in blocks that fit the device (of
images, of layers, of experts), and ends within ``check.limit_s``. With
``control`` set, the reference in that precision stands in for the served
answers, and the comparison has to call it not correct. The decode, the
block loop, the two logit comparisons and the answer line are importable
from ``check.py``.

**``floors.module``** (default ``reference/conv_floors.py``), loaded by
``cost.py::load_floors``: ``image_flops(model, row)`` and
``serve_bytes(model, row)`` for one row of the window's padding table (a
canvas and batch bucket with ``batches``, ``rows_real``, ``rows_dispatched``
and ``px_real``). Only what no implementation can avoid is counted, per
*real* image; a share of a roofline that reads over 100% says a floor
counts too much.
"""

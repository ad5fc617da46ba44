"""The rounds the harness can drive, one module each. A traffic file names
its round by dotted path (``round``; absent means
``benchmark.rounds.packed_fold``), so a new kind of round is a new file here
and an edit to none that is there.

What a round module gives, and what :mod:`benchmark.harness` takes from it:

``span_names``
    The spans the round opens inside the harness's ``round``, in order. Idle
    gaps are named by them, the run's record keeps their seconds, and the
    trace's reader keeps host events of these names. Every round opens three
    under these names, because the metrics that every cell reports read them
    (``CELL_WIDE_SPANS``): ``dispatch`` (the chunk steps handed to the
    device) and ``fold`` (the wait for the last of them; ``engine.fold_s``
    runs from the start of the one to the end of the other), and ``epilogue``
    (host work from the fetched accumulator to the revealed aggregate;
    ``epilogue.s``). Stages of its own go under names of its own (``unmask``),
    beside these and not inside them under an old name. A layer file says
    which spans it reads (``reads_spans``), and the per-cell check
    (``tests/benchmark/cell_checks.py``) refuses a cell that reports a metric
    whose span its round does not open.

``Session(cell, seed, devices, stages)``
    One cell set up on the devices handed in: the scheme built from the
    configuration file (a ``scheme.kind`` the round does not know is refused
    here, not by the harness), the input and its plain reference made on the
    device from the seed (:mod:`benchmark.reference`, which imports nothing
    of the program). ``stages`` is filled with the seconds each part took.
    It has:

    * ``run_round(index, spans, subsets=None) -> (matched, evidence)``: one
      whole round, key to comparison, under ``spans``. ``matched`` says the
      aggregate equalled the reference bit for bit; ``evidence`` is the array
      that must differ between consecutive rounds (fresh randomness);
      ``subsets`` (warm-up only) are further clerk subsets that must reveal
      the same;
    * ``warmup_subsets``: what the harness hands the warm-up round as
      ``subsets``;
    * ``devices``, ``memory_peak_bytes()``: for the result's ``device``;
    * what a layer file is told of the round (``harness.LayerContext``), which
      the chunk step's roofline is computed from (``benchmark/models.py``):
      ``chunk_bytes``, the input bytes one chunk step reads, all chips
      together; ``acc_bytes``, the bytes on one chip of everything a chunk
      step takes from the step before and hands on: the accumulator, and where
      the step carries more than an accumulator (a masked round's seeds or
      masks) that too, since the model counts these bytes read once and
      written once; ``steps_per_round``, the chunk steps of one round, by
      which the chunk step's device time is divided; ``plan``, the program's
      plan of the scheme underneath (modulus, input, randomness and share
      counts, batches), which the int8 model reads;
    * optionally ``compared() -> {name: {"value": number, "limit": number}}``,
      read once after the window: comparisons of the round's own, of what only
      it knows (a masked round: the rounds whose reveal before unmasking
      equalled the plain aggregate, the rows whose rejection slack ran out).
      The harness writes them into the line's ``compared`` after its own
      four, whose names they may not take, and a value over its limit makes
      the run not ``correct``.

``steps(cell, devices)``
    ``[(jitted, example arguments), ...]``: every program a round runs on the
    device inside the window, to lower for these (perhaps only described)
    devices, each under a name of its own (the trace tells programs apart by
    the jitted function's name). **The first is the chunk step**, the program
    run ``steps_per_round`` times a round over the resident input:
    ``chunk_step_roofline`` divides by the device time of its operations
    alone, and its largest argument is a chunk of the resident input. Holds no
    array. :mod:`benchmark.scopes` joins a trace with the compiled text of all
    of them; the compile rehearsal compiles each for a described chip and
    holds the chip to the resident input plus the largest program's
    temporaries and output.

``input_maker(cell, devices)``
    ``(jitted, example arguments)`` of the program that makes one chunk of
    the resident input in set-up, for the compile rehearsal: set-up has to
    fit the chip beside the input it has made so far.

``TRACE_WINDOW_ROUNDS`` (optional)
    The most rounds a traced window runs, where fewer than the traced window's
    seconds hold would leave the profiler more events than the host's memory
    holds (a round whose rows cross the host link: every chunk's host relayout
    is an event). A round module without it runs the traced window for its
    seconds alone (``harness.TRACE_WINDOW_SECONDS``).
"""

#: the spans every round opens, under these names: the metrics that every
#: cell reports read them
CELL_WIDE_SPANS = ("dispatch", "fold", "epilogue")

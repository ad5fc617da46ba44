"""The rounds the harness can drive, one module each. A traffic file names
its round by dotted path (``round``; absent means
``benchmark.rounds.packed_fold``), so a new kind of round is a new file here
and an edit to none that is there.

What a round module gives, and what :mod:`benchmark.harness` takes from it:

``span_names``
    The spans the round opens inside the harness's ``round``, in order. Idle
    gaps are named by them, the run's record keeps their seconds, and the
    trace's reader keeps host events of these names.

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
    * ``chunk_bytes``, ``acc_bytes``, ``steps_per_round``, ``plan``: what a
      layer file is told of the round (``harness.LayerContext``).

``steps(cell, devices)``
    ``[(jitted, example arguments), ...]``: the programs a round runs on the
    device inside the window, to lower for these (perhaps only described)
    devices. Holds no array. :mod:`benchmark.scopes` joins a trace with their
    compiled text; the compile rehearsal compiles them for a described chip.
"""

"""Names of the train step's phases (``jax.named_scope``).

Every topology's step — ``api.train_step`` and the shard-local body of
``distributed.make_sharded_train_step``, sequential or batch-parallel —
carries the same four scopes; the sharded body adds a fifth, ``VOTES``,
around the vote psum of each class round (the single-device step has no
psum to name). They are metadata only: the
compiled ops are the same with or without them. The scope path reaches
each op's ``op_name`` in the compiled program, so a device profile can
attribute an op's time to a phase by its instruction name rather than by
XLA's fusion numbering (``tests/test_tm_scopes.py`` pins that every step carries all
four, and that every all-reduce of the vote psum carries ``VOTES``;
``tests/test_tpu_compile.py`` that the v5e compiler keeps them).
"""

FEEDBACK = "tm.feedback"      # Type I/II update: the scan (or vmap) of rounds
DRAWS = "tm.draws"            # per-sample keys and uniforms, inside the rounds
EVENTS = "tm.events"          # include masks and the event-buffer selection
CACHE_SYNC = "tm.cache_sync"  # every engine cache absorbing the events
VOTES = "tm.votes"            # the per-round vote psum of the sharded sync step

"""Compressed inverted index + query serving.

Layers (bottom up):

  * ``invindex`` — per-term blocked storage: d-gapped docids + TFs compressed
    with any codec from ``repro.core.codec.REGISTRY``; lists shorter than 64
    use the Stream VByte short-list fast path.  Every 512-posting block keeps
    its first docid as a skip pointer and decodes independently.
  * ``engine`` — the batched query engine: ``engine.plan(batch)`` resolves a
    ``QueryBatch`` into a typed ``ExecutionPlan`` — placement (host / device
    / fused, with batches of at most ``HOST_BATCH_MAX`` queries auto-placed
    on the host, recorded in the plan's ``note``) plus every referenced
    term's codec capabilities, read once from the registry — and
    ``engine.execute(plan)`` runs it: AND queries fuse skip-table block
    pruning with the vectorized intersection kernels
    (``repro.kernels.intersect``), and hot decoded blocks live in an LRU
    keyed by (term, block) so a batch decodes each block at most once.
  * ``device`` — device-resident posting arenas, built *generically* from
    each codec's declared ``ArenaLayout``: the compressed blocks flattened
    into contiguous per-declared-column device arrays with per-(term, block)
    offset/length/first-docid tables.  ``engine.to_device()`` switches the
    serving path onto batched lane-parallel work-list decodes (one jitted
    call per codec per AND round, deduped across the batch); AND candidates
    then stay in a device-resident segmented bitmap across rounds
    (``repro.kernels.intersect_rounds`` — only the final result is copied to
    host), optionally decoding through the fused Pallas tile kernel
    (``repro.kernels.decode_fused``).
  * ``scores`` — the ranked-retrieval subsystem: per-(term, doc) BM25
    impacts quantized to u8 and packed as an additional score column per
    posting block (``ScoreArena``, same padded-``ArenaColumn`` contract as
    the codec arenas), with block-max / term-max / top-impact / docid-stripe
    tables precomputed for WAND/BMW-style pruning.  ``or`` / ``and_scored``
    plans accumulate the codes into a segmented device score buffer
    (``repro.kernels.topk``) and sync one compacted candidate bitmap per
    batch.
  * ``segments`` — the streaming mutable layer: ``DeltaSegment`` (a small
    doc-major mutable segment absorbing inserts/upserts) and ``Tombstones``
    (a versioned dead-docid set with frozen memoized views) sit beside the
    immutable ``Generation``; ``InvertedIndex`` composes the three into a
    mutable handle that serves bit-identically to a from-scratch rebuild.
  * ``shards`` — doc-range sharding: one generation split at contiguous
    docid boundaries into per-shard self-contained generations whose BM25 /
    quantizer statistics are pinned to the parent's, so the sharded serving
    path (``engine.to_device(shards=N)`` / ``mesh=``) runs every round
    shard-local and merges ranked top-k with one collective (see the sharded
    serving walkthrough further down).
  * ``serve`` — latency-governed online serving on top of ``engine``: an
    async admission queue + dynamic batcher turning a request *stream* into
    the ``QueryBatch``-shaped work everything below is built for (see the
    serving walkthrough further down).

Streaming mutation (insert -> tombstone -> compact -> generation swap):
``InvertedIndex`` wraps one immutable ``Generation`` (gid-stamped: blocks,
skip tables, impact tables, and the cached device arena all belong to a
generation) plus the mutable delta/tombstone pair.  ``insert(docid, terms,
doclen)`` lands in the delta segment — a docid the generation already holds
is tombstoned first (the *shadowing invariant*: generation and delta doc
sets stay disjoint, so result unions are plain sorted merges).  ``delete``
drops delta copies outright and tombstones base copies (their blocks are
immutable; serving gates them out).  Serving under mutation pins a frozen
*epoch* (``(gid, tombstone version, delta version)``) per ``plan()`` /
``execute()``: generation results are tombstone-filtered (on the resident
placements via ONE packed live-bitmap AND after the seed round —
``intersect_rounds.pack_live_words``, one upload per epoch, zero downloads)
and merged with a brute-force scan of the small delta segment; BM25 stats
(df, doclen, avdl) are recomputed live per epoch so scores match a rebuild
bitwise.  Ranked modes under a delta-bearing epoch disarm block-max pruning
(the quantized tables carry generation-time stats) — the candidate superset
contract still holds, and the exact float rescore restores bit-identity —
but TOMBSTONE-ONLY epochs (the common few-deletes case) stay armed: deletes
only shrink df, so every live/generation idf ratio is >= 1, and a per-query
Q16.16 deflation ``iq = floor(2**16 / Rmax)`` applied to every threshold
comparison keeps the generation-time upper bounds sound against live scores
(the full derivation is the re-arm note in ``index/scores.py``; theta0 is
re-derived from the tombstone-filtered top-code tables via
``ScoreArena.theta0_live``, and ``benchmarks/bench_query.py --mutate``
tracks ``ranked_tomb_1pct.blocks_pruned > 0`` as the CI guarantee);
``compact()`` fully re-arms pruning: it merge-sorts generation-minus-tombstones
with the delta per term, re-encodes through the codec registry into
generation ``gid + 1``, and swaps it in atomically — in-flight plans keep
executing against their pinned generation's arenas (all engine caches are
keyed by gid / epoch, so nothing stale survives the swap).  The governing
**rebuild-parity contract**: at any epoch, every mode on every placement is
bitwise identical to ``InvertedIndex.build(doclen_now(), live_postings)``
(enforced by the stateful differential harness in ``tests/test_mutation.py``
and the segment-consistency registry lint; ``benchmarks/bench_query.py
--mutate`` tracks qps per tombstone density, compaction pause, and
delta-scan overhead).

Ranked retrieval (score columns, quantization contract, block-max pruning):
``ScoreArena`` quantizes with a single global scale ``delta = max impact /
255`` and ``code = floor(impact / delta)``; floor is monotone, so the stored
block-max tables equal the maxima of the stored codes (the registry lint
cross-checks this), and for a query of ``m`` known term occurrences any
doc's true score S obeys ``C*delta <= S < (C+m)*delta`` around its quantized
sum C.  Two consequences anchor exactness: the k-th largest quantized sum
``theta`` lower-bounds the k-th best true score, so the device path syncs
the candidate set ``{C >= theta - m}`` (as a bitmap, once per batch) and
rescores it with the shared float oracle — top-k sets and scores match the
host BM25 path bitwise, ties broken by ascending docid — and an OR
(term, block) work-list entry is *pruned* before decode when its upper bound
(own block-max + every other occurrence's max code over the block's docid
range, read from the per-term docid-stripe tables + the margin m) cannot
reach the threshold: first the static theta0 (the k-th top impact code of
the query's strongest term) on the host, then — **adaptive BMW theta** —
a per-query threshold PROMOTED on device after every round (the pooled
k-th statistic of the accumulated sums, ``kernels/topk.pooled_threshold``,
a sound monotone lower bound on the final k-th sum), which each later
round's kernels re-test against every entry's staged upper bound so the
work-list compacts itself with zero per-round host syncs.  Pruned blocks
only lose contributions of docs provably outside the true top-k.
``and_scored`` reuses the AND machinery — the intersection bitmap gates the
score scatter on device and is never downloaded.
``benchmarks/bench_query.py`` tracks ``blocks_pruned`` / ``blocks_scored``
/ ``blocks_dense`` and per-round host syncs (zero on the resident ranked
path) per mode.

Density-adaptive bitmap blocks (word-parallel dense postings):
posting blocks whose docids are dense — average gap (span / count) at most
``repro.core.dense_bitmap.DENSE_GAP``, fitting one 128-word window at a
4-word-aligned phase — are stored as RAW 128-word bitmaps instead of
d-gap-compressed streams, per "SIMD Compression and the Intersection of
Sorted Integers": at that density the fastest intersect is a word-parallel
AND of the bitmap against the candidate window, with no unpack and no
prefix-sum at all.  The decision is made once per block at build time
(``invindex.Generation.build`` asks ``dense_bitmap.eligible(ids)``) and the
chosen representation travels as a *declared capability*, never an engine
branch: ``dense_bitmap`` is a registered codec whose ``ArenaLayout``
declares ``bitmap_words`` / ``is_bitmap`` alongside the ordinary two-column
(ctrl, data) contract, so

  * the conformance harness / registry lint round-trip it like any codec
    (a ``"raw"`` wire fallback keeps it total on ineligible streams, and
    the lint checks the density boundary cases: exactly-at-threshold,
    singleton, window-overflow);
  * the device arena (``index/device.py``) and score arena
    (``index/scores.py``) notice ``is_bitmap(block)`` at staging time and
    keep, per dense block, its 128-word window + window origin ``w0``
    (4-word aligned, so column ``w0 * 32`` is a 128-lane-aligned slice) —
    plus, on the score side, a packed 4096-position code window;
  * the engine routes each (term, block) work-list entry by a dict lookup
    (``dense_slot``) into the word-parallel round kernels
    (``intersect_rounds.dense_round_accumulate``,
    ``topk.dense_score_round``) while sparse blocks of the same query take
    the decode path in the same round — exact composition, since each block
    owns disjoint docids (``benchmarks/bench_query.py`` counts the
    dense-served entries as ``blocks_dense``).

Mixed dense/sparse lists therefore fall out of the registry machinery with
zero engine special cases, and a new density policy is one codec swap.

Online serving (admission -> batch -> plan -> execute, SLO semantics):
``serve.IndexServer`` fronts one ``QueryEngine`` with an async admission
queue and a dynamic batcher.  A ``Request(terms, mode, k, tenant,
deadline_ms)`` is admitted into its tenant's bounded queue (each tenant's
share of ``queue_cap`` is proportional to its configured weight; over-share
-> explicit ``Rejected("queue_full")``, already-spent deadline ->
``Rejected("expired")`` — backpressure is always an explicit result, never
a silent stall).  The batcher seeds each batch with the earliest-deadline
pending request (EDF) and fills it by smooth weighted round-robin with
*compatible* requests only — same ``(mode, k)``; mixed modes never co-batch
— closing on size (``max_batch``) OR time (earliest member deadline minus
``slack_ms``, capped by the seed's ``max_wait_ms`` so a lone request on an
idle queue still flushes promptly), whichever hits first.  Members whose
deadline passed while queued are shed at close (``Rejected("deadline")``);
the survivors become ONE ``QueryBatch`` through the ordinary
``engine.plan()/execute()`` discipline, so served results are bitwise the
offline path's and the plan's pinned epoch makes a racing ``compact()``
invisible.  A request that starts in time but finishes late is served, not
shed — it counts against ``on_time_frac`` / ``goodput_qps`` instead of
``shed_rate``.  ``start()`` warms the hottest terms' decoded-block + score
caches and primes the jit buckets before the first real request.  Every
request leaves a five-stamp ``TraceRecord`` (enqueue <= close <= plan <=
execute <= done — monotonicity is registry-linted) and every batch a
replayable ``BatchRecord`` in ``ServerStats``; ``snapshot()`` derives
p50/p99/p999 latency, goodput, shed rate, and the per-placement batch-size
histogram.  ``benchmarks/bench_serving.py`` drives seeded Poisson and
bursty (Gamma) open-loop streams through all of this into a committed
baseline at the repo root (the smoke run asserts zero shed under Poisson
and bitwise oracle parity), and
``python -m repro.launch.serve --index --smoke`` is the end-to-end entry
point.

Sharded multi-device serving (doc-range partitioning, margin-preserving
merge): ``engine.to_device(shards=N)`` (or ``mesh=launch.mesh.serving_mesh(N)``
to pin one shard per device, ``bounds=(0, ..., n_docs)`` for explicit —
possibly uneven or empty — splits) partitions the generation **doc-wise by
contiguous docid ranges** (``index/shards.py``; ``ShardSpec.derive`` balances
per-tile posting mass read off the skip tables alone).  Doc-wise is the
partitioning under which every per-round kernel is already shard-local: a
doc's postings for *every* term live in exactly one shard, so AND candidate
bitmaps and ranked score accumulators never reference another shard's docids
— rounds run with ZERO inter-device traffic, and each shard is an ordinary
single-device ``QueryEngine`` over its slice (own arenas, skip / block-max /
stripe tables, caches).  The one subtlety is statistics: each shard
generation is rebuilt over its local docid space but with the parent's
(df, n_docs, avdl, global max impact) pinned (``shard_generation``'s fixup;
registry-linted), so per-(term, doc) quantized codes are bitwise the
unsharded arena's and per-shard quantized sums are globally comparable.
Ranked merge: every shard reports its local k-th quantized sum (ONE
all-gather of (theta, count) pairs per batch — under a mesh via
``jax.shard_map`` + ``distributed.collectives.merge_topk_stats``, else a
host stack); the merged threshold ``max_s(theta_s)`` lower-bounds the global
k-th sum, so applying the ordinary quantization-margin contract
*shard-locally* at that threshold keeps the union of per-shard candidate
bitmaps a guaranteed superset of the float top-k, and the shared block-lazy
float rescore restores bit-identity with the unsharded host oracle (every
mode, every placement — ``tests/test_sharded.py``).  Adaptive theta
promotion starts from the max pooled theta0 across shards (the argmax shard
really holds k docs reaching it); tombstone gates are sliced at shard
boundaries (``intersect_rounds.pack_live_words_range``); mutation epochs pin
per-shard generation sets atomically — the shard set is cached ON the
generation, so a racing ``compact()`` builds a fresh set for gid+1 while
in-flight plans keep serving the old one; ``plan.note`` records the shard
topology.  ``benchmarks/bench_query.py`` tracks the scaling curves per
shard count (qps per mode, merge syncs and collective bytes per ranked
batch, and cross-shard round syncs — ZERO by construction).

Observability (spans, typed metrics, the perf-regression gate): the
``repro.obs`` package is the one instrumentation layer over everything
above.

  * **Spans** (``repro.obs.trace``): the serving lifecycle is recorded on
    the server's own always-enabled ``Tracer`` — ``serve/request``
    (admission -> delivery, one detached span per request whose endpoints
    ARE the ``TraceRecord``'s enqueue/done stamps), ``serve/close`` (batch
    forming), and ``serve/batch`` with ``serve/plan`` / ``serve/execute`` /
    ``serve/deliver`` children that tile it exactly, so an exported trace
    accounts for 100% of measured batch wall-clock (the CI smoke asserts
    >= 90% via ``trace_coverage``).  Deep engine and kernel spans —
    ``engine/plan``, ``engine/execute``, ``and/seed``, ``and/round``,
    ``and/tomb_gate``, ``ranked/round``, ``ranked/tomb_gate``,
    ``ranked/rescore``, ``sharded/merge``, ``decode/<codec>``,
    ``decode/fused``, ``kernel/extract_ids``, ``kernel/topk`` — go through
    the process-global tracer (``repro.obs.enable_tracing()``), DISABLED by
    default so the resident hot paths pay one attribute check; sub-engines
    stamp their own ``shard<i>`` lane.  Every resident round splits into
    host stages: ``round/rows`` (the dedupe and the arena decode into one
    matrix per source), ``round/stack`` (the round's index vectors), both on
    a memo miss, and ``round/launch`` (the accumulate / commit kernels), nested
    under ``and/seed``, ``and/round`` or ``ranked/round``.  While tracing is
    on, a ``jax.monitoring`` listener records ``jax/trace``, ``jax/lower``
    and ``jax/compile`` spans under whatever span was open, so compiles
    inside a round show as such.  ``to_chrome_trace(stats.tracer, get_tracer())``
    exports Chrome trace-event JSON loadable directly at
    https://ui.perfetto.dev (one named track per lane: serve / engine /
    shard<i> / device); ``python -m repro.launch.serve --index --smoke
    --trace-out trace.json`` is the one-command path (CI uploads it as the
    ``trace_smoke`` artifact).  Fenced device timing (``--fenced`` /
    ``enable_tracing(True, fenced=True)``) brackets round spans with
    ``jax.block_until_ready`` so durations attribute device wall-clock to
    the producing kernel — off by default, keeping the zero-sync
    discipline untouched.
  * **Typed metrics** (``repro.obs.metrics``): every engine owns a
    ``MetricsRegistry`` of declared counters (labels drawn from the fixed
    ``LABEL_KEYS`` vocabulary: engine / shard / placement / mode / codec /
    tenant / outcome; duplicate registration raises; schema consistency
    across instances is registry-linted via ``lint_metrics``).  The old
    free-form ``engine.dev_stats`` dict survives as a live READ-ONLY view
    (``DevStatsView``) over the same counters.  The device arena's counts
    (``device_calls``, ``blocks_device``, ``blocks_host``, ``fused_calls``,
    ``fused_blocks``, ``decode_postings``, ``rows_sliced``,
    ``rows_stacked``, ``rows_gathered``, ``rows_padded``) are counters of
    the registry the engine binds to it, so ``dev_stats`` carries them and
    ``arena.stats`` is a view of them too; the last four say how a round's
    rows reach the kernels: cut or stacked one at a time (the numpy
    -fallback blocks), gathered by row index from a decoded matrix, or
    bucket padding.  Per-call assertions use
    scoped sampling — ``with engine.metrics.scoped() as s: ...;
    s.delta("worklist_decodes")`` — instead of hand-rolled before/after
    subtraction.  ``ServerStats`` carries its own registry
    (requests/batches/latency by tenant + outcome) with Prometheus 0.0.4
    text exposition: ``stats.snapshot(prometheus=True)`` or ``launch.serve
    --metrics-out``.  Latency percentiles use the deterministic
    nearest-rank rule (``repro.obs.metrics.nearest_rank``) so tiny-n
    snapshots are reproducible observed values, monotone in q.
  * **Perf-regression gate** (``repro.obs.regress`` +
    ``tools/bench_gate.py``): the query, mutation and serving baselines
    that ``benchmarks/`` commits at the repo root are enforced contracts —
    CI regenerates them at the smoke workload, then every shared ``*qps*``
    leaf must hold ``fresh >= baseline * min_ratio`` (floors in the
    committed tolerances file, default 0.55) and the deterministic
    invariants are re-checked hard:
    ``cand_syncs == 0`` / ``score_syncs == 0`` on the resident paths,
    ``blocks_pruned > 0`` under 1% tombstones, decode dedup <= 1 per hot
    block, zero cross-shard round syncs, zero Poisson shed, bitwise serving
    parity.  ``bench_gate.py --self-test`` proves the gate has teeth by
    synthesizing a 2x qps regression and asserting it fails (which pins
    every floor into (0.5, 1.0]).

Adding a codec (protocol v2): implement ``encode(np.uint32[N]) -> Encoded``
and ``decode_np(Encoded) -> np.uint32[N]`` and register a
``repro.core.codec.Codec`` in ``repro/core/codec.py``.  Capabilities are
*declared*, not special-cased:

  * add a ``JaxDecode(args, scalar, vec)`` capability and the codec joins the
    scalar-vs-SIMD decode benchmarks and differential tests;
  * add an ``ArenaLayout`` (named padded ``ArenaColumn`` streams for one
    512-posting block + a fixed-shape ``decode_block(*column_slices,
    *column_lens, n_valid)``) and the codec's blocks decode natively in the
    device arena's batched work-lists — the arena, engine, parity tests
    (``tests/test_device_arena.py`` derives its sweep from the declarations),
    and the CI registry lint (``tools/registry_lint.py``) pick it up with no
    engine edits.  Most codecs need only the classic (ctrl, data) pair —
    declare it with the ``ArenaLayout.two_column(...)`` alias and a
    ``decode_block(ctrl, data, ctrl_len, n_valid)``.

Exception columns: a codec whose encoder patches outliers through a separate
stream (non-empty ``Encoded.exceptions`` — the Group-PFD family) must declare
a third column named ``"exceptions"`` whose ``extract`` pulls the patch
words, and apply the patch *inside* ``decode_block`` (see
``repro/core/group_pfd.py::decode_arena_block``: unpack the low bits, then a
fixed-lane vectorized ``gather_bits`` + masked scatter of (position, value)
pairs — one lane per potential exception, masked past the block's dynamic
total, so the patch never leaves the device).  Width the column for the worst
case the encoder can emit (``group_pfd.ARENA_EXC_WORDS``: every integer an
exception at the widest value width).  The registry lint round-trips a
heavy-tailed probe through every arena codec and fails any that stores
exceptions without declaring such a column, so a forgotten column is caught
in CI rather than as silently-unpatched decodes.

Migration note (v1 codec surface): ``CodecSpec`` and its ``decode`` /
``jax_args`` / ``decode_jax_scalar`` / ``decode_jax_vec`` attributes ->
``Codec`` with ``decode_np`` and the ``jax`` / ``arena`` capability objects
(old attributes remain as read-only aliases).
"""

from . import (device, engine, invindex, scores, serve,  # noqa: F401
               shards)

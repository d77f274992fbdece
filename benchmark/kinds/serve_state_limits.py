"""kind `serve_state_limits`: kind `serve_family_limits`' run, and besides
the served tokens' logits the STATE that the server keeps of a sequence,
held to limits that the traffic file states.

Why: a layer may keep a state whose part of the logits is too small at the
harness's random weights to move a served token.  falcon_h1's SSM state is
the first: at N(0, 0.02) weights x, B and C leave the convolution of order
1e-3 and the state's part of the mixer's output is ~1e-6 of the skip term
`D x`, so a state kept in bfloat16 instead of the float32 the
configuration states, a state not carried from one chunk to the next, or a
decode step that never writes its slot back all pass every logit limit
(PERF.md, section 6).  What such a fault does move is the state itself.

So once the window and the traced slice are over and the clients have
stopped, the engine - idle, its pools still on the device - runs PROBES
(`probe`): one prompt of each length of the traffic (ids drawn from the
seed), each decoding up to `max(max_tokens) - 2` tokens (the request must
not finish: a finished request's slot goes back to the free list).  With
the step in flight read back, each state group's slot of each probe is
copied to the host in every layer; then the pools are freed as before.
The reference's `states(params, ids, n, config)` gives what the same `n`
positions leave (the prompt and every served token but the last, which no
step has fed yet), and the reading of a group is the worst, over probes,
layers and the first axis of the slot (a head of the SSM state, a tap of
the convolution's inputs), of

  |served state - reference state| / |reference state|   (Frobenius)

A head apart, not the slot whole: a state rounded to bfloat16 at every
position errs most in the heads of longest memory, whose decay per
position (dt A ~ 0.001) lies under bfloat16's resolution, while every head
reads the bfloat16 activations' error (~0.5%) alike.

The traffic file holds

  reference_limits.state_rel_err   {group: the largest reading allowed}
  state_axes                       {group: the axes of the served slot in
                                    the reference's order} (a transposed
                                    layout; a group absent: the same)

and `correct` gains `reference_state`: every group of the limits read, at
most its limit.  BENCH_REFERENCE_FAULT=<name>[,<name>..] also reads the
same served states against the reference computed with each fault, and
logs those readings beside the fault's logit readings with the verdict of
all the checks: what PERF.md states as the readings behind each limit.

`run` is kind `serve_family_limits`', with kind `serve_family_paths`'
`free_pools` (which runs once the clients have stopped, before the
reference) swapped for one that probes first, and kind
`serve_family_limits`' `compare` and `readings` for ones that also read
the states and keep the logit readings of the faults.  Those files belong
to the benchmark as accepted; a PR of another kind may not edit them.
"""
import os
import random
import time

from benchmark.kinds import serve_family_limits as limits_kind
from benchmark.kinds import serve_family_paths as paths
from benchmark.kinds.serve_family_limits import _swapped, verdict
from benchmark.lib import family
from benchmark.lib.common import fold_seed, log

# a probe's prompt ids: the seed folded with this, apart from the traffic's
PROBE_SALT = 7919
# what a probe may run for, its prefills and decode steps together
PROBE_LIMIT_S = 300.0


def probe(engine, traffic, vocab_size, seed):
    """Run the probes on the idle `engine` (module docstring) ->
    [(ids, n, {group: [host array a layer]})]."""
    import jax
    import numpy as np

    from paddle_tpu.serving.scheduler import SamplingParams

    for rid in list(engine._requests):
        engine.release_request(rid)
    rng = random.Random(fold_seed(seed) + PROBE_SALT)
    new = max(v for v, _ in traffic["max_tokens"])
    lengths = sorted({v for v, _ in traffic["prompt_len"]}, reverse=True)
    prompts = [[rng.randrange(vocab_size) for _ in range(n)]
               for n in lengths]
    rids = [engine.add_request(p, SamplingParams(max_new_tokens=new))
            for p in prompts]
    deadline = time.perf_counter() + PROBE_LIMIT_S
    # one step may be in flight behind the one read back: stop two short
    while min(len(engine._requests[r].output_ids) for r in rids) < new - 2:
        if time.perf_counter() > deadline:
            raise RuntimeError("the state probes did not decode in "
                               f"{PROBE_LIMIT_S} s")
        engine.step()
    engine.settle()
    out = []
    for rid, prompt in zip(rids, prompts):
        req = engine._requests[rid]
        if req.finished:
            raise RuntimeError(f"state probe {rid} finished before it was "
                               "read")
        ids = prompt + list(req.output_ids)
        held = {}
        for group, cache in engine.states.items():
            slot = np.int32(cache.slot_of(rid))
            held[group] = [np.asarray(a, np.float32) for a in
                           jax.device_get([s[slot] for s in cache.state])]
        out.append((ids, len(ids) - 1, held))
    for rid in rids:
        engine.release_request(rid)
    return out


def state_readings(model, config, probes, traffic, fault=None):
    """{group: the worst relative error over probes, layers and the first
    axis of a slot} of the probes' served states against the reference's,
    computed with `fault`; None without probes."""
    import numpy as np

    if not probes:
        return None
    ref = family.reference(config)
    params = ref.params_from_model(model)
    axes = traffic.get("state_axes", {})
    width = -(-max(len(ids) for ids, _, _ in probes) // 128) * 128
    worst = {}
    for ids, n, held in probes:
        row = np.zeros((width,), np.int32)
        row[:len(ids)] = ids
        want = ref.states(params, row, n, config, fault=fault)
        for group, layers in held.items():
            for got, w in zip(layers, want):
                w = np.asarray(w[group], np.float64)
                got = np.asarray(got, np.float64)
                if group in axes:
                    got = got.transpose(axes[group])
                rows = len(w)
                err = (np.linalg.norm((got - w).reshape(rows, -1), axis=1)
                       / np.maximum(np.linalg.norm(w.reshape(rows, -1),
                                                   axis=1), 1e-30))
                err = float(err.max()) if np.isfinite(err).all() \
                    else float("inf")
                worst[group] = max(worst.get(group, 0.0), err)
    return worst


def state_verdict(read, limits):
    """`reference_state`: every group of `state_rel_err` read, each at most
    its limit."""
    want = limits["state_rel_err"]
    return read is not None and all(
        g in read and read[g] <= lim for g, lim in want.items())


def _brief(read):
    return ", ".join(f"{g} {v:.5f}" for g, v in sorted(read.items()))


def run(spec):
    tr = spec["traffic"]
    limits = tr["reference_limits"]
    got = {}
    logit_reads = []          # `readings` of the sound reference, then of
    #                           each fault of BENCH_REFERENCE_FAULT in order
    free_pools = paths.free_pools
    compare = limits_kind.compare
    readings = limits_kind.readings

    def probing_free_pools(engine):
        t0 = time.perf_counter()
        try:
            got["probes"] = probe(engine, tr,
                                  int(spec["config"]["vocab_size"]),
                                  spec["seed"])
            log(f"state probes: states read after "
                f"{[n for _, n, _ in got['probes']]} positions, in "
                f"{time.perf_counter() - t0:.1f} s")
        except Exception as e:     # no reading: `reference_state` fails
            log(f"the state probes failed: {e!r}")
        free_pools(engine)

    def kept_readings(margins, near):
        read = readings(margins, near)
        logit_reads.append(read)
        return read

    def compare_states(model, config, records, traffic, seed):
        read = compare(model, config, records, traffic, seed)
        state = state_readings(model, config, got.get("probes"), traffic)
        got["state"] = state
        log(f"state check on {len(got.get('probes') or ())} probes: worst "
            f"relative error {_brief(state or {})} (limits "
            f"{limits['state_rel_err']})")
        faults = os.environ.get("BENCH_REFERENCE_FAULT", "")
        for i, fault in enumerate(filter(None, faults.split(","))):
            fs = state_readings(model, config, got.get("probes"), traffic,
                                fault)
            logits = (logit_reads[1 + i] if len(logit_reads) > 1 + i
                      else None)
            checks = dict(verdict(logits, limits),
                          reference_state=state_verdict(fs, limits))
            log(f"the same states under the reference with fault "
                f"{fault!r}: worst relative error {_brief(fs or {})}; "
                f"checks {checks} -> correct {all(checks.values())}")
        return read

    with _swapped(paths, free_pools=probing_free_pools), \
            _swapped(limits_kind, compare=compare_states,
                     readings=kept_readings):
        out = limits_kind.run(spec)
    out["checks"]["reference_state"] = state_verdict(got.get("state"),
                                                     limits)
    return out

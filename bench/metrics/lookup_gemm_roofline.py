"""The lookup GEMM's share of its roofline over the traced window: the
least time the chip could take for the window's lookup-GEMM work over the
device time under the program's ``repro.lookup_gemm`` scope, whatever
implements the GEMM.

The work is ``counting.lookup_gemm_cost`` for every serve linear of every
layer (q, k, v, o; the SwiGLU's wi, wg and wo) at the rows each forward
computes: ``slots`` per decode forward and ``chunk`` per prefill chunk,
padding included, since the kernel does that work (``batch_occupancy``
reports the padding).  The quantisation is the function's defaults,
which are the configuration's (G 4, int16 indices, N_arr 4096).  The
least time is the larger of the ops over the int8 peak and the bytes over
HBM bandwidth.  A program without the scope leaves nothing to read."""

from bench import counting

SCOPE = "repro.lookup_gemm"


def scopes(run):
    return (SCOPE,)


def linears(d: dict) -> list:
    """(K, N) of one decoder layer's serve linears."""
    dm, ff = d["d_model"], d["d_ff"]
    q, kv = d["n_heads"] * d["head_dim"], d["n_kv"] * d["head_dim"]
    return [(dm, q), (dm, kv), (dm, kv), (q, dm),
            (dm, ff), (dm, ff), (ff, dm)]


def forward_cost(d: dict, M: int) -> tuple:
    """(ops, bytes) of one forward's lookup GEMMs at M rows."""
    ops = nbytes = 0
    for K, N in linears(d):
        o, b = counting.lookup_gemm_cost(M, K, N)
        ops += o
        nbytes += b
    return d["n_layers"] * ops, d["n_layers"] * nbytes


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t = run.trace["scope_s"].get(SCOPE, 0.0)
    if not t:
        return None
    least = 0.0
    for M, n in ((run.slots, run.decode_steps),
                 (run.chunk, run.prefill_tokens_run // run.chunk)):
        ops, nbytes = forward_cost(run.dims, M)
        least += n * max(ops / run.peaks["int8_ops_per_s"],
                         nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t

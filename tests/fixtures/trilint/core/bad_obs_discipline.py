"""trilint fixture: deliberate obs-discipline violation (D1).

Parsed, never imported.  The first span wraps a kernel launch, is not
named as a dispatch and never blocks — under JAX's async dispatch it
measures enqueue latency and passes it off as device time.  The second
span blocks on the result and is compliant; the third is named as a
dispatch, so it says it times the enqueue; the fourth wraps pure-host
work and needs neither.
"""
import jax


def chunk_count_kernel(src, dst):  # stand-in kernel (naming convention)
    return src + dst


def save_stuff(path, data):  # host work: returns only when done
    return len(data)


def unsynced(obs, adj, chunk):
    # D1: kernel launch inside a span that is neither a dispatch nor blocks.
    with obs.span("count.kernel", cat="engine"):
        part = chunk_count_kernel(chunk, adj)
    return part


def synced(obs, adj, chunk):
    # compliant: the launch result is materialized before the span exits.
    with obs.span("count.kernel", cat="engine"):
        part = jax.block_until_ready(chunk_count_kernel(chunk, adj))
    return part


def dispatched(obs, adj, chunks):
    # compliant: a dispatch span times the enqueue and says so.
    with obs.span("engine.dispatch", cat="engine"):
        parts = [chunk_count_kernel(c, adj) for c in chunks]
    return parts


def host_only(obs, data):
    # compliant: host work is synchronous; no block required.
    with obs.span("ingest.cache_write", cat="io"):
        save_stuff("/tmp/x", data)

"""Durable serving demo: SIGKILL a serving worker mid-wave, restore it.

A child process serves a batch of requests on a durable
:class:`~repro.core.serving.SynergyServer` — every accepted request and
every emitted token hits a write-ahead journal before it is visible, and
crash-consistent snapshots land through the seed Checkpointer on a step
cadence.  The parent SIGKILLs the child mid-generation (a real kill -9,
no cleanup handlers run), then calls ``SynergyServer.restore`` on the
same directory: the latest snapshot loads, the journal suffix replays
(every recomputed token verified bitwise against the record), and the
restored server finishes every request.  The printed streams match a
never-crashed reference exactly — served once, lost never.

The child also installs :func:`~repro.soc.install_sigterm_drain`, so a
polite ``SIGTERM`` (instead of the demo's ``SIGKILL``) would drain
gracefully: finish live generations, snapshot, close the journal.

    PYTHONPATH=src python examples/durable_serving.py
"""

import os
import subprocess
import sys
import tempfile
import textwrap

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

N_REQ, NEW_TOKENS, PLEN = 6, 12, 4

#: the worker: a durable server that snapshots every 4 steps and prints
#: a heartbeat per step so the parent can kill it demonstrably mid-wave
_WORKER = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp
    from repro.configs import ARCHS, reduced
    from repro.core.serving import Request, SynergyServer
    from repro.models import init_model
    from repro.soc import Durability, install_sigterm_drain

    cfg = reduced(ARCHS["granite-3-2b"], n_layers=2, d_model=32,
                  n_heads=2, d_ff=64, vocab=128)
    params = init_model(cfg, jax.random.key(0))
    srv = SynergyServer(cfg, params, slots=3, max_len=32, prefill_len=4,
                        durable=Durability(sys.argv[1], snapshot_every=4))
    install_sigterm_drain(srv)        # SIGTERM would drain gracefully...
    for i in range(6):
        srv.submit(Request(i, jnp.arange(4, dtype=jnp.int32) + 3 * i,
                           max_new_tokens=12))
    while srv.step():                 # ...but SIGKILL gets no warning
        print("step", srv.stats.engine_steps, "tokens",
              srv.stats.tokens_out, flush=True)
""")


def reference(cfg, params):
    import jax.numpy as jnp
    from repro.core.serving import Request, SynergyServer
    srv = SynergyServer(cfg, params, slots=3, max_len=32, prefill_len=4)
    reqs = [Request(i, jnp.arange(4, dtype=jnp.int32) + 3 * i,
                    max_new_tokens=NEW_TOKENS) for i in range(N_REQ)]
    for r in reqs:
        srv.submit(r)
    srv.run()
    return {r.rid: list(r.out) for r in reqs}


def main():
    # a device belongs to one process at a time: the parent stays off JAX
    # (no jax or repro import) until the worker has exited
    workdir = tempfile.mkdtemp(prefix="durable-serving-")
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = (os.path.abspath(src) + os.pathsep
                         + env.get("PYTHONPATH", ""))

    print(f"== worker serving {N_REQ} requests into {workdir}")
    child = subprocess.Popen([sys.executable, "-c", _WORKER, workdir],
                             stdout=subprocess.PIPE, text=True, env=env)
    for line in child.stdout:          # kill -9 mid-generation
        print("  worker:", line.strip())
        if "tokens" in line and int(line.split()[-1]) >= 11:
            child.kill()
            break
    child.wait()
    print(f"== worker SIGKILLed (rc={child.returncode}) — no cleanup ran")

    import jax
    from repro.configs import ARCHS, reduced
    from repro.core.serving import SynergyServer
    from repro.models import init_model
    from repro.soc import Durability, RequestJournal
    cfg = reduced(ARCHS["granite-3-2b"], n_layers=2, d_model=32,
                  n_heads=2, d_ff=64, vocab=128)
    params = init_model(cfg, jax.random.key(0))

    records, _, torn = RequestJournal.scan(
        os.path.join(workdir, "journal.bin"))
    print(f"== journal: {len(records)} records"
          + (" + torn tail (truncated on restore)" if torn else ""))

    print("== restoring: latest snapshot + journal-suffix replay")
    srv = SynergyServer.restore(
        cfg, params, durable=Durability(workdir, snapshot_every=4),
        slots=3, max_len=32, prefill_len=4)
    print(f"   replayed {srv.stats.replayed_tokens} already-delivered "
          f"tokens (verified bitwise), resuming fresh serving")
    srv.run()

    ref = reference(cfg, params)
    print("== streams after crash + restore vs never-crashed reference:")
    ok = True
    for rid in sorted(srv.restored_requests):
        got = list(srv.restored_requests[rid].out)
        match = got == ref[rid]
        ok &= match
        print(f"   rid {rid}: {got} {'== reference' if match else '!= '}"
              + ("" if match else str(ref[rid])))
    stats = srv.close()
    print(f"== served exactly once: {ok};  fresh tokens "
          f"{stats.tokens_out}, replayed {stats.replayed_tokens}, "
          f"snapshots {stats.snapshots}, restores {stats.restores}")
    assert ok


if __name__ == "__main__":
    main()

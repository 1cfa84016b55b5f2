"""The load generator: a child process that never imports JAX.

Standard library only.  It speaks HTTP to 127.0.0.1, streams
``/v1/completions`` with token-id prompts (``temperature 0``,
``ignore_eos``, ``return_token_ids``) and writes one record per request:
when it was due, when it was sent, the time of every SSE event and the
tokens in it, the HTTP status, and how it ended.  The parent holds the chip
and the server; this process cannot contend for the parent's interpreter
lock.

Timeline (``time.monotonic``, which parent and child share on Linux):

    t_start ---- pre-roll ---- t_window ---- the window ---- t_end [drain]

The same traffic runs through both phases without a pause, so the window
opens on a busy server.  The first line of stdout is the timeline as JSON;
the parent reads it and acts at those times.  An open loop sends a request
when it is DUE whether or not earlier ones have ended, and after ``t_end``
waits (at most ``--drain-s``) for the window's requests to end.  A closed
loop keeps ``--clients`` requests outstanding and cuts what is in flight at
``t_end``: those requests are recorded as ``cut``, not as failed.

Run as a script by the parent: ``python benchmark/harness/loadgen.py ...``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import traffic  # noqa: E402  (sibling module; no package import, no JAX)

READ_LIMIT = 1 << 22


def request_body(model: str, ids: list, max_tokens: int) -> bytes:
    return json.dumps({
        "model": model, "prompt": ids, "max_tokens": max_tokens,
        "temperature": 0, "ignore_eos": True, "stream": True,
        "return_token_ids": True}).encode()


async def stream_completion(host: str, port: int, body: bytes, rec: dict,
                            timeout: float) -> None:
    """POST one streamed completion and fill ``rec`` in place."""
    writer = None
    try:
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection(host, port, limit=READ_LIMIT), timeout)
        head = (f"POST /v1/completions HTTP/1.1\r\nHost: {host}:{port}\r\n"
                "Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n")
        rec["sent"] = time.monotonic()
        writer.write(head.encode() + body)
        await writer.drain()
        status = await asyncio.wait_for(reader.readline(), timeout)
        rec["status"] = int(status.split()[1])
        while (await asyncio.wait_for(reader.readline(), timeout)).strip():
            pass                                   # response headers
        if rec["status"] != 200:
            rec["error"] = (await reader.read(2048)).decode("replace")[:300]
            return
        events = rec["events"]
        while True:
            line = await asyncio.wait_for(reader.readline(), timeout)
            if not line:
                rec["error"] = "stream ended without [DONE]"
                return
            if not line.startswith(b"data:"):
                continue                           # chunk framing, blanks
            payload = line[5:].strip()
            if payload == b"[DONE]":
                rec["done"] = True
                return
            now = time.monotonic()
            event = json.loads(payload)
            if "error" in event:
                rec["error"] = str(event["error"])[:300]
                continue
            n = sum(len(c.get("token_ids") or ())
                    for c in event.get("choices", ()))
            if n:
                events.append((now, n))
    except asyncio.CancelledError:
        rec["cut"] = True
        raise
    except (OSError, asyncio.TimeoutError, ValueError, IndexError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
    finally:
        rec["end"] = time.monotonic()
        if writer is not None:
            writer.close()


class Source:
    """The run's requests, phase by phase, in this seed's order."""

    def __init__(self, mix: dict, args):
        self.mix, self.args = mix, args
        self.n = traffic.pool_size(mix, args.rate, args.seconds)
        self.sizes = {ph: traffic.sizes_for(mix, args.seed, ph, self.n)
                      for ph in ("preroll", "window")}
        self.next_index = {"preroll": 0, "window": 0}

    def make(self, phase: str, index: int, due: float) -> tuple:
        prompt, out = self.sizes[phase][index % self.n]
        ids = traffic.prompt_ids(self.args.seed, phase, index, prompt,
                                 self.args.vocab)
        rec = {"id": f"{phase}-{index}", "phase": phase, "due": due,
               "prompt_tokens": prompt, "want": out, "events": []}
        return rec, request_body(self.args.model, ids, out)

    def take(self, phase: str, due: float) -> tuple:
        index = self.next_index[phase]
        self.next_index[phase] = index + 1
        return self.make(phase, index, due)


async def closed_loop(src: Source, args, times: dict, records: list) -> None:
    async def client(k: int) -> None:
        # clients join one after the other over --ramp seconds, so that the
        # run does not open with every client's prefill at once
        start = times["t_start"] + args.ramp * k / max(1, args.clients)
        await asyncio.sleep(max(0.0, start - time.monotonic()))
        while True:
            now = time.monotonic()
            if now >= times["t_end"]:
                return
            phase = "window" if now >= times["t_window"] else "preroll"
            rec, body = src.take(phase, now)
            records.append(rec)
            await stream_completion(args.host, args.port, body, rec,
                                    args.request_timeout)
            if not rec["events"]:
                await asyncio.sleep(0.05)      # refused: do not spin

    tasks = [asyncio.ensure_future(client(k)) for k in range(args.clients)]
    await asyncio.sleep(max(0.0, times["t_end"] - time.monotonic()))
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


async def open_loop(src: Source, args, times: dict, records: list) -> None:
    # every request is built before the schedule starts: nothing but
    # sending happens on the clock
    plan = []
    n_pre = traffic.pool_size(src.mix, args.rate, args.preroll)
    for phase, t0, n, span in (
            ("preroll", times["t_start"], n_pre, args.preroll),
            ("window", times["t_window"], src.n, args.seconds)):
        if span <= 0:
            continue
        offsets = traffic.arrivals_for(src.mix, args.seed, phase, n, span)
        for i, off in enumerate(offsets):
            plan.append(src.make(phase, i, t0 + off))
    plan.sort(key=lambda p: p[0]["due"])
    tasks = []
    for rec, body in plan:
        delay = rec["due"] - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        records.append(rec)
        tasks.append(asyncio.ensure_future(stream_completion(
            args.host, args.port, body, rec, args.request_timeout)))
    if tasks:
        deadline = times["t_end"] + args.drain_s
        _, pending = await asyncio.wait(
            tasks, timeout=max(0.0, deadline - time.monotonic()))
        for t in pending:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--mix", required=True, help="traffic file")
    ap.add_argument("--model", required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--preroll", type=float, required=True)
    ap.add_argument("--rate", type=float, default=0.0)
    ap.add_argument("--clients", type=int, default=0)
    ap.add_argument("--ramp", type=float, default=0.0,
                    help="closed loop: seconds over which the clients join")
    ap.add_argument("--drain-s", type=float, default=30.0)
    ap.add_argument("--request-timeout", type=float, default=120.0)
    ap.add_argument("--start-in", type=float, default=0.5,
                    help="seconds from now to t_start")
    ap.add_argument("--out", required=True, help="records file (JSON)")
    args = ap.parse_args(argv)

    mix = traffic.load_mix(args.mix)
    src = Source(mix, args)
    t_start = time.monotonic() + args.start_in
    times = {"t_start": t_start, "t_window": t_start + args.preroll,
             "t_end": t_start + args.preroll + args.seconds}
    print(json.dumps(times), flush=True)
    records: list = []
    loop = closed_loop if mix["loop"] == "closed" else open_loop

    async def go() -> None:
        await asyncio.sleep(max(0.0, t_start - time.monotonic()))
        await loop(src, args, times, records)

    asyncio.run(go())
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"times": times, "records": records}, f)
    os.replace(tmp, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())

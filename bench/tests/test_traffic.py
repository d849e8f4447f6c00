"""The traffic generator and the client's timing."""

import asyncio
import json
import time

import numpy as np
import pytest

import loadgen
from conftest import BENCH
from generators import sessions

CHAT = json.loads((BENCH / "traffic" / "chat.json").read_text())


def shapes(plan):
    return [(s.index, s.system, round(s.arrival, 9),
             [(len(t.user), t.answer, t.think_s) for t in s.turns])
            for s in plan.sessions]


def test_same_seed_same_sessions():
    mix = CHAT
    a = sessions.generate(mix, 2**33 + 7, 30.0, 1000)
    b = sessions.generate(mix, 2**33 + 7, 30.0, 1000)
    assert shapes(a) == shapes(b)
    assert all((x.user == y.user).all()
               for s, r in zip(a.sessions, b.sessions)
               for x, y in zip(s.turns, r.turns))
    c = sessions.generate(mix, 8, 30.0, 1000)
    # another seed: the same sessions at the same times, other token ids
    assert shapes(c) == shapes(a)
    assert not all((x.user == y.user).all()
                   for s, r in zip(a.sessions, c.sessions)
                   for x, y in zip(s.turns, r.turns))
    assert not all((x == y).all() for x, y in zip(a.system_prompts,
                                                  c.system_prompts))


def test_lognormal_clips_and_context():
    mix = CHAT
    got = sessions.session_shapes(mix, 3000)
    users = np.array([u for _, turns in got for u, _, _ in turns])
    answers = np.array([a for _, turns in got for _, a, _ in turns])
    for v, spec in ((users, mix["user"]), (answers, mix["answer"])):
        assert v.min() >= spec["min"] and v.max() <= spec["max"]
        assert abs(np.median(v) / spec["median"] - 1) < 0.1
    for _, turns in got:
        assert turns, "every session has a first turn"
        context = mix["system_len"] if mix["system_prompts"] else 0
        for u, a, _ in turns:
            context += u + a
        assert context <= mix["engine"]["max_len"]


def test_zipf_system_prompt_shares():
    got = sessions.session_shapes(CHAT, 8000)
    share = np.bincount([s for s, _ in got], minlength=8) / len(got)
    want = sessions.zipf_weights(8, CHAT["zipf_s"])
    assert np.allclose(share, want, atol=0.02)
    assert share[0] > 2.5 * share[3]


def test_arrivals_scale_with_the_rate():
    slow = sessions.arrivals(dict(CHAT, session_rate=0.5), 60.0)
    fast = sessions.arrivals(dict(CHAT, session_rate=1.0), 60.0)
    assert (np.diff(slow) > 0).all() and slow[-1] < 60.0
    assert np.allclose(fast[:len(slow)] * 2, slow[:len(fast)])
    assert len(fast) > len(slow)


# -- timing against a fake server ---------------------------------------------


class FakeServer:
    """Streams ``max_new_tokens`` tokens for every request, ``gap`` s
    apart; holds back the first token of requests whose prompt starts
    with a ``stall`` id by ``stall_s``, and never answers those starting
    with a ``mute`` id."""

    def __init__(self, gap=0.002, stall_s=0.3):
        self.gap, self.stall_s = gap, stall_s

    async def handle(self, reader, writer):
        await reader.readline()
        n = 0
        while (h := await reader.readline()) not in (b"\r\n", b""):
            if h.lower().startswith(b"content-length"):
                n = int(h.split(b":")[1])
        body = json.loads(await reader.readexactly(n))
        writer.write(b"HTTP/1.1 200 OK\r\n\r\n")
        head = body["prompt"][0]
        if head == 999:       # mute
            await asyncio.sleep(3600)
        if head == 998:       # stall
            await asyncio.sleep(self.stall_s)
        for i in range(body["max_new_tokens"]):
            writer.write(f'data: {{"token": {i}}}\n\n'.encode())
            await writer.drain()
            await asyncio.sleep(self.gap)
        writer.write(b'event: done\ndata: {"finish_reason": "length"}\n\n')
        await writer.drain()
        writer.close()


def plan_of(heads, arrivals, answer=4):
    turns = [[sessions.Turn(user=np.array([h, 1, 2], np.int32), answer=answer,
                            think_s=0.0)] for h in heads]
    return sessions.Plan(system_prompts=[], sessions=[
        sessions.Session(index=i, system=-1, turns=t, arrival=a)
        for i, (t, a) in enumerate(zip(turns, arrivals))])


def drive(plan, seconds, on_send=None):
    async def main():
        srv = await asyncio.start_server(FakeServer().handle, "127.0.0.1", 0)
        port = srv.sockets[0].getsockname()[1]
        t0 = time.monotonic() + 0.02
        recs = await loadgen.drive("127.0.0.1", port, plan, t0, seconds,
                                   on_send=on_send)
        srv.close()
        return t0, recs

    return asyncio.run(main())


def test_server_stall_shows_in_ttft():
    t0, recs = drive(plan_of([5, 998, 5], [0.0, 0.05, 0.1]), 1.0)
    ttft = loadgen.ttfts(recs, t0 + 1.0)
    assert ttft[1] >= 0.3 and ttft[0] < 0.2 and ttft[2] < 0.2
    assert loadgen.failed(recs, t0 + 1.0) == 0


def test_client_stall_counts_from_due_time():
    """A client that stalls sends late; the wait counts from when each
    request was due, and the lateness shows."""
    blocked = []

    def block(rec):  # the first send holds the loop for 0.3 s
        if not blocked:
            blocked.append(rec)
            time.sleep(0.3)

    t0, recs = drive(plan_of([5, 5, 5], [0.0, 0.1, 0.2]), 1.0, block)
    recs.sort(key=lambda r: r.due)
    end = t0 + 1.0
    ttft = loadgen.ttfts(recs, end)
    late = loadgen.lateness(recs, end)
    assert late[1] >= 0.15 and late[2] >= 0.05
    assert ttft[1] >= 0.15          # due at 0.1, sent after 0.3
    sent_based = recs[1].times[0] - recs[1].sent
    assert sent_based < ttft[1] - 0.1


def test_unserved_requests_stay_in_the_tail():
    t0, recs = drive(plan_of([5, 999, 5], [0.0, 0.1, 0.2]), 0.6)
    end = t0 + 0.6
    ttft = np.sort(loadgen.ttfts(recs, end))
    assert len(ttft) == 3
    assert ttft[-1] == pytest.approx(0.5, abs=0.05)  # waited until the close
    assert np.percentile(ttft, 95) > 0.4
    gaps = loadgen.token_gaps(recs, end)
    assert gaps.size == 6 and gaps.max() < 0.1
    assert loadgen.tokens_in(recs, t0, end) == 8
    assert loadgen.failed(recs, end) == 0

"""The closed loop's start: with `ramp_lane_gap_s` the first lane goes alone
and the others follow one by one once its first chunk has arrived; without
it all lanes go at once, as before. Played against a server of a dozen
lines that keeps the order in which requests reached it."""
import http.server
import json
import sys
import threading
import time

import pytest

import client

FIRST_S, TOKENS, CLIENTS = 0.3, 3, 4


class Handler(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.0"
    arrived = []

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        Handler.arrived.append((time.time(), body["prompt"]))
        time.sleep(FIRST_S)
        self.send_response(200)
        self.end_headers()
        for _ in range(body["max_new_tokens"]):
            self.wfile.write(json.dumps({"response": "a", "done": False}).encode() + b"\n")
            self.wfile.flush()
            time.sleep(0.01)
        self.wfile.write(b'{"done": true}\n')

    def log_message(self, *a):
        pass


def play(tmp_path, monkeypatch, gap):
    Handler.arrived = []
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    sched = {"loop": "closed", "clients": CLIENTS, "seconds": 0.5, "lead_in_s": 1.0,
             "drain_s": 5.0, "request_deadline_s": 5.0, "ramp_lane_gap_s": gap,
             "prewarm": [],
             "requests": [{"idx": i, "client": (i * 3 + 1) % CLIENTS, "order": 0,
                           "model": "m", "system": "", "prompt": f"lane{i}",
                           "max_new_tokens": TOKENS} for i in range(CLIENTS)]}
    (tmp_path / "s.json").write_text(json.dumps(sched))
    out = tmp_path / "r.json"
    monkeypatch.setattr(sys, "argv", ["client.py", str(tmp_path / "s.json"),
                                      "127.0.0.1", str(srv.server_address[1]), str(out)])
    try:
        assert client.main() == 0
    finally:
        srv.shutdown()
        srv.server_close()
    recs = sorted(json.loads(out.read_text())["records"], key=lambda r: r["idx"])
    assert all(r["done"] and len(r["chunk_t"]) == TOKENS for r in recs)
    return recs


def test_ramp_sends_the_lanes_in_order_after_the_first_chunk(tmp_path, monkeypatch):
    recs = play(tmp_path, monkeypatch, 0.05)
    first = recs[0]["chunk_t"][0]
    assert [p for _, p in sorted(Handler.arrived)] == [f"lane{i}" for i in range(CLIENTS)]
    for n, r in enumerate(recs[1:], 1):
        assert r["sent"] - first == pytest.approx(n * 0.05, abs=0.03)
        assert r["due"] <= r["sent"] < r["due"] + 0.01  # due when sent, as before


def test_without_the_key_all_lanes_start_at_once(tmp_path, monkeypatch):
    recs = play(tmp_path, monkeypatch, None)
    assert max(r["sent"] for r in recs) - min(r["sent"] for r in recs) < 0.1
    assert min(r["chunk_t"][0] for r in recs) - max(r["sent"] for r in recs) > FIRST_S / 2

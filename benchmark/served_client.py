"""One client of the served cell: a JAX-free process with one ``FlightClient``
on its own connection. Standard input carries JSON lines: first the server
and the statements, then orders, each answered by one JSON line on standard
output:

``{"volley": i, "at": t}``       send statement ``i`` once at ``t`` (warm-up:
                                 all clients at the same instant)
``{"walk": [...], "start": t0, "end": t1}``
                                 the closed loop: walk the cycle again
                                 and again, each request sent when the last
                                 was answered, until ``t1``

Times are on the monotonic clock the processes of one machine share. A reply
lists every request as [statement, sent, done, answer, stage times, error]
and each distinct answer once, as Arrow IPC bytes in base64."""
from __future__ import annotations

import base64
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    from benchmark.compare import ipc_bytes
    from nds_tpu.service.frontdoor import FlightClient
    spec = json.loads(sys.stdin.readline())
    client = FlightClient(spec["host"], spec["port"],
                          timeout_s=spec["timeout_s"], retries=0)
    client.ping()
    print("READY", flush=True)

    def request(ui: int, requests: list, answers: list, ids: dict) -> None:
        unit, sql = spec["statements"][ui]
        sent = time.monotonic()
        try:
            table, resp = client.query(sql, label=unit)
        except Exception as e:   # a failed request is a result, not a crash
            requests.append([ui, sent, time.monotonic(), None, None,
                             f"{type(e).__name__}: {e}"])
            return
        done = time.monotonic()
        payload = ipc_bytes(table)
        if (ui, payload) not in ids:
            ids[(ui, payload)] = len(answers)
            answers.append([ui, base64.b64encode(payload).decode()])
        requests.append([ui, sent, done, ids[(ui, payload)],
                         resp.get("stats"), None])

    for line in sys.stdin:
        order = json.loads(line)
        requests, answers, ids = [], [], {}
        start = order.get("at", order.get("start"))
        while time.monotonic() < start:
            time.sleep(0.0005)
        if "volley" in order:
            request(order["volley"], requests, answers, ids)
        else:
            step = 0
            while time.monotonic() < order["end"]:
                request(order["walk"][step % len(order["walk"])],
                        requests, answers, ids)
                step += 1
        print(json.dumps({"requests": requests, "answers": answers}),
              flush=True)
    client.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

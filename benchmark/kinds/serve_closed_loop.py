"""Traffic kind ``serve_closed_loop``: clients that wait for their reply.

``clients`` callers (``"slots"``: one per engine slot) each send their next
request the moment the last one ends, so the engine is never short of work
and a slower engine receives less of it. Offline generation, evaluation
and synthetic-data jobs are this loop. Each client draws its requests from
its own seeded stream, so the work a client brings does not depend on the
order in which clients finish; its first request is met mid-way
(``traffic.closed_loop_client``), so the window opens on the mix's steady
state. Judged are the requests in flight at some instant of the window;
what is still running when it closes is cut by the window, not failed.
"""

from benchmark import serve_loop, traffic


class Feeder(serve_loop.Feeder):
    def __init__(self, ctx, eng, tracker, shapes):
        import deepspeed_tpu.serving as serving
        super().__init__(eng, tracker)
        self.Request = serving.Request
        n = ctx.traffic["clients"]
        n = eng.spec.slots if n == "slots" else int(n)
        self.clients = [traffic.closed_loop_client(
            ctx.traffic, ctx.seed, c, shapes["vocab_size"],
            shapes["seq_scale"])
            for c in range(n)]
        self.sent = [0] * n

    def _send(self, client):
        prompt, new = next(self.clients[client])
        request = self.Request(("c", client, self.sent[client]), prompt,
                               max_new_tokens=new)
        self.sent[client] += 1
        self.submit(request)

    def start(self, t_start):
        for client in range(len(self.clients)):
            self._send(client)

    def after_step(self, finished, t):
        # however a request ended, its client has its reply and sends on
        for request in finished:
            if request.rid[0] == "c":
                self._send(request.rid[1])


def run(ctx):
    return serve_loop.run(ctx, Feeder)

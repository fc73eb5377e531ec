"""Thin HTTP client for one serve replica (router/supervisor side).

One connection per call (``http.client``, stdlib only): the fleet's
request volume is batched device work, not connection churn, and a
fresh connection is what makes "the replica died mid-request" a clean,
*typed* failure instead of a wedged keep-alive socket.

Failure classes the router dispatches on:

- :class:`ReplicaDown` — the TCP/HTTP exchange failed before a complete
  response arrived (refused, reset, remote disconnected): the request
  may safely be retried on another replica (the device never confirmed
  executing it — and flow inference on identical inputs is idempotent
  anyway, so even a duplicated execution cannot corrupt a stream);
- :class:`ReplicaTimeout` — the per-attempt socket deadline passed: the
  replica is up but not answering (hung handler, wedged dispatch loop);
- an ordinary ``(status, meta, body)`` return for everything else,
  including typed shed/error statuses — interpreting those is routing
  policy, not transport.
"""

import http.client
import json
import socket
from urllib.parse import urlsplit

from . import wire as fwire


class ReplicaDown(ConnectionError):
    """Transport to the replica failed before a full response."""


class ReplicaTimeout(TimeoutError):
    """The replica did not answer within the per-attempt deadline."""


class ReplicaClient:
    def __init__(self, url, timeout_s=5.0):
        parts = urlsplit(url)
        self.host = parts.hostname or "127.0.0.1"
        self.port = int(parts.port or 80)
        self.url = f"http://{self.host}:{self.port}"
        self.timeout_s = float(timeout_s)

    def _request(self, method, path, body=None, meta=None, timeout=None):
        """One exchange → ``(status, meta dict, body bytes)``."""
        conn = http.client.HTTPConnection(
            self.host, self.port,
            timeout=(self.timeout_s if timeout is None else float(timeout)))
        headers = {}
        if meta is not None:
            headers[fwire.META_HEADER] = fwire.dumps_meta(meta)
        try:
            conn.request(method, path, body=body, headers=headers)
            resp = conn.getresponse()
            data = resp.read()
            raw = resp.getheader(fwire.META_HEADER)
            out_meta = json.loads(raw) if raw else None
            if out_meta is None and data \
                    and (resp.getheader("Content-Type") or "").startswith(
                        "application/json"):
                try:
                    out_meta = json.loads(data)
                except ValueError:
                    out_meta = None
            return resp.status, out_meta, data
        except socket.timeout as e:
            raise ReplicaTimeout(
                f"{self.url}{path}: no response within "
                f"{timeout or self.timeout_s} s") from e
        except (ConnectionError, http.client.HTTPException, OSError) as e:
            raise ReplicaDown(f"{self.url}{path}: {e}") from e
        finally:
            conn.close()

    # -- observability plane -------------------------------------------------

    def health(self, timeout=None):
        """``(payload, status)`` from /healthz (503 is a *valid* answer:
        not-ready or draining, as opposed to unreachable)."""
        status, meta, _ = self._request("GET", "/healthz", timeout=timeout)
        return meta or {}, status

    def status(self, timeout=None):
        status, meta, _ = self._request("GET", "/statusz", timeout=timeout)
        if status != 200:
            raise ReplicaDown(f"{self.url}/statusz: HTTP {status}")
        return meta or {}

    # -- serving API ---------------------------------------------------------

    def flow(self, meta, body, timeout=None):
        """One inference exchange → ``(status, meta, body)``."""
        return self._request("POST", "/v1/flow", body=body, meta=meta,
                             timeout=timeout)

    def drain(self, timeout=None):
        status, meta, _ = self._request("POST", "/drainz", timeout=timeout)
        return meta or {}, status

    def export_session(self, client, timeout=None):
        """The replica's carry snapshot for ``client``, or None."""
        status, meta, _ = self._request(
            "GET", f"/sessionz?client={client}", timeout=timeout)
        if status != 200 or not isinstance(meta, dict) \
                or "data" not in meta:
            return None
        return meta

    def import_session(self, snapshot, timeout=None):
        payload = json.dumps(snapshot).encode()
        status, meta, _ = self._request("POST", "/sessionz", body=payload,
                                        timeout=timeout)
        return status == 200

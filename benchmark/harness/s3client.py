"""The load generator's S3 client and SigV4 signer.

A copy of what the generator needs from `garage_tpu/api/s3/client.py` and
`garage_tpu/api/common/signature.py` (client side only), kept here so that
a later PR can change the program's client without changing the yardstick.
Imports neither JAX nor numpy nor anything of `garage_tpu`.
"""

from __future__ import annotations

import hashlib
import hmac
import urllib.parse
from datetime import datetime, timezone

import aiohttp

ALGORITHM = "AWS4-HMAC-SHA256"


def _uri_encode(s: str, encode_slash: bool = True) -> str:
    return urllib.parse.quote(s, safe="-_.~" if encode_slash else "-_.~/")


def _hmac(key: bytes, msg: str) -> bytes:
    return hmac.new(key, msg.encode(), hashlib.sha256).digest()


def sign_headers(
    method: str, path: str, headers: dict[str, str], body: bytes,
    key_id: str, secret: str, region: str,
) -> dict[str, str]:
    """`headers` (lowercase names, `host` among them) plus x-amz-date,
    x-amz-content-sha256 and authorization.  No query string: the
    generator's requests have none."""
    now = datetime.now(timezone.utc)
    timestamp, date = now.strftime("%Y%m%dT%H%M%SZ"), now.strftime("%Y%m%d")
    h = dict(headers)
    h["x-amz-date"] = timestamp
    payload_hash = hashlib.sha256(body).hexdigest()
    h["x-amz-content-sha256"] = payload_hash
    signed = sorted(h)
    canon = "\n".join([
        method.upper(),
        _uri_encode(path, encode_slash=False),
        "",
        "".join(f"{n}:{' '.join(h[n].split())}\n" for n in signed),
        ";".join(signed),
        payload_hash,
    ])
    scope = f"{date}/{region}/s3/aws4_request"
    to_sign = "\n".join(
        [ALGORITHM, timestamp, scope, hashlib.sha256(canon.encode()).hexdigest()]
    )
    key = _hmac(_hmac(_hmac(_hmac(("AWS4" + secret).encode(), date), region), "s3"), "aws4_request")
    sig = hmac.new(key, to_sign.encode(), hashlib.sha256).hexdigest()
    h["authorization"] = (
        f"{ALGORITHM} Credential={key_id}/{scope}, "
        f"SignedHeaders={';'.join(signed)}, Signature={sig}"
    )
    return h


class S3Client:
    """One endpoint, one aiohttp session.  `request` returns the raw
    (status, headers, body): the generator judges every answer itself."""

    def __init__(self, endpoint: str, key_id: str, secret: str, region: str = "garage"):
        self.endpoint = endpoint.rstrip("/")
        self.host = urllib.parse.urlparse(self.endpoint).netloc
        self.key_id, self.secret, self.region = key_id, secret, region
        self._session: aiohttp.ClientSession | None = None

    async def close(self) -> None:
        if self._session is not None:
            await self._session.close()
            self._session = None

    async def request(self, method: str, path: str, body: bytes = b"") -> tuple[int, dict, bytes]:
        if self._session is None:
            # no total timeout: the generator bounds each request itself
            self._session = aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=None),
                connector=aiohttp.TCPConnector(limit=0),
            )
        signed = sign_headers(
            method, path, {"host": self.host}, body, self.key_id, self.secret, self.region
        )
        async with self._session.request(
            method, self.endpoint + urllib.parse.quote(path), data=body,
            headers=signed, skip_auto_headers=["Content-Type"],
        ) as resp:
            data = await resp.read()
            return resp.status, resp.headers.copy(), data  # case-insensitive

"""Distribution families beyond the round-1 core set.

Reference: python/paddle/distribution/{independent,transformed_distribution,
multivariate_normal,student_t,cauchy,chi2,binomial,continuous_bernoulli,
lkj_cholesky,exponential_family}.py. Semantics follow the reference (which
matches torch.distributions closely); tests golden-check against torch CPU.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import random as _rng
from ..ops import apply_op
from ..tensor import Tensor
from . import Beta, Distribution, Gamma, register_kl
from .transform import _cod, _dom


def _val(x):
    return x._value if isinstance(x, Tensor) else jnp.asarray(x, jnp.float32)


def _sum_rightmost(x, n):
    for _ in range(n):
        x = x.sum(-1)
    return x


class ExponentialFamily(Distribution):
    """Base class marker for exponential-family distributions.

    Reference: distribution/exponential_family.py — provides a Bregman
    entropy default from natural parameters; concrete families here override
    entropy in closed form, so this is the API-parity base only.
    """


class Independent(Distribution):
    """Reinterpret rightmost batch dims of `base` as event dims.

    Reference: distribution/independent.py."""

    def __init__(self, base, reinterpreted_batch_rank):
        self.base = base
        self.reinterpreted_batch_rank = int(reinterpreted_batch_rank)
        if not 0 <= self.reinterpreted_batch_rank <= len(base.batch_shape):
            raise ValueError(
                "reinterpreted_batch_rank must be in [0, base batch rank]")
        shape = tuple(base.batch_shape) + tuple(base.event_shape)
        split = len(base.batch_shape) - self.reinterpreted_batch_rank
        super().__init__(shape[:split], shape[split:])

    @property
    def mean(self):
        return self.base.mean

    @property
    def variance(self):
        return self.base.variance

    def sample(self, shape=()):
        return self.base.sample(shape)

    def rsample(self, shape=()):
        return self.base.rsample(shape)

    def log_prob(self, value):
        lp = self.base.log_prob(value)
        return apply_op(lambda v: _sum_rightmost(v, self.reinterpreted_batch_rank),
                        "independent_sum", lp)

    def entropy(self):
        ent = self.base.entropy()
        return apply_op(lambda v: _sum_rightmost(v, self.reinterpreted_batch_rank),
                        "independent_sum", ent)


class TransformedDistribution(Distribution):
    """Distribution of t_n(...t_1(x)), x ~ base.

    Reference: distribution/transformed_distribution.py."""

    def __init__(self, base, transforms):
        self.base = base
        self.transforms = list(transforms)
        # propagate the event rank through the chain: a transform needs at
        # least its domain rank of event dims, and maps them to its codomain
        # rank (rank-changing links like Reshape compose correctly)
        ev = len(base.event_shape)
        for t in self.transforms:
            ev = max(ev, _dom(t)) - _dom(t) + _cod(t)
        shape = tuple(base.batch_shape) + tuple(base.event_shape)
        for t in self.transforms:
            shape = t.forward_shape(shape)
        split = len(shape) - ev
        super().__init__(shape[:split], shape[split:])

    def sample(self, shape=()):
        x = self.base.sample(shape)
        for t in self.transforms:
            x = t.forward(x)
        return x

    def rsample(self, shape=()):
        x = getattr(self.base, "rsample", self.base.sample)(shape)
        for t in self.transforms:
            x = t.forward(x)
        return x

    def log_prob(self, value):
        # stays in Tensor ops end to end so tape gradients flow to transform
        # parameters (normalizing-flow MLE) and to `value`
        event_dim = len(self._event_shape)
        ldj_sum = None
        y = value
        for t in reversed(self.transforms):
            x = t.inverse(y)
            dom = _dom(t)
            event_dim += dom - _cod(t)
            ldj = t.forward_log_det_jacobian(x)
            red = apply_op(
                lambda v, n=event_dim - dom: _sum_rightmost(v, n),
                "sum_rightmost", ldj)
            ldj_sum = red if ldj_sum is None else ldj_sum + red
            y = x
        base_lp = self.base.log_prob(y)
        lp = apply_op(
            lambda v, n=event_dim - len(self.base.event_shape):
            _sum_rightmost(v, n), "sum_rightmost", base_lp)
        return lp if ldj_sum is None else lp - ldj_sum


class MultivariateNormal(Distribution):
    """Reference: distribution/multivariate_normal.py."""

    def __init__(self, loc, covariance_matrix=None, precision_matrix=None,
                 scale_tril=None, name=None):
        self.loc = _val(loc)
        given = [a is not None
                 for a in (covariance_matrix, precision_matrix, scale_tril)]
        if sum(given) != 1:
            raise ValueError("exactly one of covariance_matrix / "
                             "precision_matrix / scale_tril is required")
        if scale_tril is not None:
            self.scale_tril = _val(scale_tril)
        elif covariance_matrix is not None:
            self.scale_tril = jnp.linalg.cholesky(_val(covariance_matrix))
        else:
            prec = _val(precision_matrix)
            # chol(P^-1) via inverting the cholesky factor of P
            lp = jnp.linalg.cholesky(prec)
            eye = jnp.eye(prec.shape[-1], dtype=prec.dtype)
            linv = jax.scipy.linalg.solve_triangular(lp, eye, lower=True)
            self.scale_tril = jnp.linalg.cholesky(
                jnp.swapaxes(linv, -1, -2) @ linv)
        d = self.loc.shape[-1]
        batch = np.broadcast_shapes(self.loc.shape[:-1],
                                    self.scale_tril.shape[:-2])
        super().__init__(batch, (d,))

    @property
    def mean(self):
        return Tensor(jnp.broadcast_to(
            self.loc, self._batch_shape + self._event_shape))

    @property
    def covariance_matrix(self):
        return Tensor(self.scale_tril @ jnp.swapaxes(self.scale_tril, -1, -2))

    @property
    def variance(self):
        var = jnp.square(self.scale_tril).sum(-1)
        return Tensor(jnp.broadcast_to(
            var, self._batch_shape + self._event_shape))

    def sample(self, shape=()):
        shape = tuple(shape) + self._batch_shape + self._event_shape
        z = jax.random.normal(_rng.next_key(), shape,
                              dtype=jnp.result_type(self.loc))
        return Tensor(self.loc + jnp.einsum("...ij,...j->...i",
                                            self.scale_tril, z))

    rsample = sample

    def log_prob(self, value):
        def f(v):
            d = self._event_shape[0]
            diff = v - self.loc
            # solve_triangular does not broadcast batch dims: align explicitly
            tril = jnp.broadcast_to(
                self.scale_tril,
                diff.shape[:-1] + self.scale_tril.shape[-2:])
            m = jax.scipy.linalg.solve_triangular(
                tril, diff[..., None], lower=True)[..., 0]
            half_log_det = jnp.log(
                jnp.diagonal(self.scale_tril, axis1=-2, axis2=-1)).sum(-1)
            return (-0.5 * (d * math.log(2 * math.pi)
                            + (m * m).sum(-1)) - half_log_det)

        return apply_op(f, "mvn_log_prob", value)

    def entropy(self):
        d = self._event_shape[0]
        half_log_det = jnp.log(
            jnp.diagonal(self.scale_tril, axis1=-2, axis2=-1)).sum(-1)
        ent = 0.5 * d * (1 + math.log(2 * math.pi)) + half_log_det
        return Tensor(jnp.broadcast_to(ent, self._batch_shape))

    def kl_divergence(self, other):
        d = self._event_shape[0]
        half_log_det_p = jnp.log(
            jnp.diagonal(self.scale_tril, axis1=-2, axis2=-1)).sum(-1)
        half_log_det_q = jnp.log(
            jnp.diagonal(other.scale_tril, axis1=-2, axis2=-1)).sum(-1)
        # tr(Σq^-1 Σp) = |Lq^-1 Lp|_F^2 ; maha = |Lq^-1 (μp-μq)|^2
        batch = np.broadcast_shapes(self._batch_shape, other._batch_shape)
        d2 = other.scale_tril.shape[-2:]
        lq = jnp.broadcast_to(other.scale_tril, batch + d2)
        lq_inv_lp = jax.scipy.linalg.solve_triangular(
            lq, jnp.broadcast_to(self.scale_tril, batch + d2), lower=True)
        tr = jnp.square(lq_inv_lp).sum((-2, -1))
        diff = jnp.broadcast_to(self.loc - other.loc, batch + d2[-1:])
        m = jax.scipy.linalg.solve_triangular(
            lq, diff[..., None], lower=True)[..., 0]
        maha = (m * m).sum(-1)
        return Tensor(0.5 * (tr + maha - d)
                      + half_log_det_q - half_log_det_p)


class StudentT(Distribution):
    """Reference: distribution/student_t.py."""

    def __init__(self, df, loc=0.0, scale=1.0, name=None):
        self.df = _val(df)
        self.loc = _val(loc)
        self.scale = _val(scale)
        super().__init__(np.broadcast_shapes(
            self.df.shape, self.loc.shape, self.scale.shape))

    @property
    def mean(self):
        return Tensor(jnp.broadcast_to(
            jnp.where(self.df > 1, self.loc, jnp.nan), self._batch_shape))

    @property
    def variance(self):
        v = jnp.where(
            self.df > 2,
            jnp.square(self.scale) * self.df / (self.df - 2),
            jnp.where(self.df > 1, jnp.inf, jnp.nan))
        return Tensor(jnp.broadcast_to(v, self._batch_shape))

    def sample(self, shape=()):
        shape = tuple(shape) + self._batch_shape
        t = jax.random.t(_rng.next_key(), self.df, shape)
        return Tensor(self.loc + self.scale * t)

    rsample = sample

    def log_prob(self, value):
        def f(v):
            from jax.scipy.special import gammaln

            df, scale = self.df, self.scale
            z = (v - self.loc) / scale
            const = (gammaln(0.5 * (df + 1)) - gammaln(0.5 * df)
                     - 0.5 * jnp.log(df * math.pi) - jnp.log(scale))
            return const - 0.5 * (df + 1) * jnp.log1p(jnp.square(z) / df)

        return apply_op(f, "student_t_log_prob", value)

    def entropy(self):
        from jax.scipy.special import digamma, gammaln

        df = self.df
        lbeta = gammaln(0.5 * df) + math.lgamma(0.5) - gammaln(0.5 * (df + 1))
        ent = (jnp.log(self.scale)
               + 0.5 * (df + 1) * (digamma(0.5 * (df + 1)) - digamma(0.5 * df))
               + 0.5 * jnp.log(df) + lbeta)
        return Tensor(jnp.broadcast_to(ent, self._batch_shape))


class Cauchy(Distribution):
    """Reference: distribution/cauchy.py (mean/variance undefined -> raise)."""

    def __init__(self, loc, scale, name=None):
        self.loc = _val(loc)
        self.scale = _val(scale)
        super().__init__(np.broadcast_shapes(self.loc.shape, self.scale.shape))

    @property
    def mean(self):
        raise ValueError("Cauchy distribution has no mean")

    @property
    def variance(self):
        raise ValueError("Cauchy distribution has no variance")

    def sample(self, shape=()):
        shape = tuple(shape) + self._batch_shape
        z = jax.random.cauchy(_rng.next_key(), shape)
        return Tensor(self.loc + self.scale * z)

    rsample = sample

    def log_prob(self, value):
        def f(v):
            z = (v - self.loc) / self.scale
            return (-math.log(math.pi) - jnp.log(self.scale)
                    - jnp.log1p(jnp.square(z)))

        return apply_op(f, "cauchy_log_prob", value)

    def cdf(self, value):
        def f(v):
            return jnp.arctan((v - self.loc) / self.scale) / math.pi + 0.5

        return apply_op(f, "cauchy_cdf", value)

    def entropy(self):
        return Tensor(jnp.broadcast_to(
            jnp.log(4 * math.pi * self.scale), self._batch_shape))

    def kl_divergence(self, other):
        # closed form (Chyzak & Nielsen 2019)
        t1 = jnp.square(self.scale + other.scale)
        t2 = jnp.square(self.loc - other.loc)
        return Tensor(jnp.log((t1 + t2) / (4 * self.scale * other.scale)))


class Chi2(Gamma):
    """Chi-squared = Gamma(df/2, rate=1/2). Reference: distribution/chi2.py."""

    def __init__(self, df, name=None):
        df = _val(df)
        super().__init__(0.5 * df, jnp.full_like(df, 0.5)
                         if df.shape else jnp.float32(0.5))

    @property
    def df(self):
        return Tensor(2 * self.concentration)


class Binomial(Distribution):
    """Reference: distribution/binomial.py."""

    def __init__(self, total_count, probs, name=None):
        self.total_count = _val(total_count).astype(jnp.float32)
        self.probs = _val(probs)
        super().__init__(np.broadcast_shapes(
            self.total_count.shape, self.probs.shape))

    @property
    def mean(self):
        return Tensor(jnp.broadcast_to(
            self.total_count * self.probs, self._batch_shape))

    @property
    def variance(self):
        return Tensor(jnp.broadcast_to(
            self.total_count * self.probs * (1 - self.probs),
            self._batch_shape))

    def sample(self, shape=()):
        shape = tuple(shape) + self._batch_shape
        # jax.random.binomial mixes a f32 literal into lax.clamp internally,
        # which breaks under the global x64 flag (f64 operands) — sample in
        # plain f32 with x64 off; the return dtype is f32 either way
        n = jnp.asarray(self.total_count, jnp.float32)
        p = jnp.asarray(self.probs, jnp.float32)
        with jax.enable_x64(False):
            out = jax.random.binomial(_rng.next_key(), n, p, shape=shape)
        return Tensor(out.astype(jnp.float32))

    def log_prob(self, value):
        def f(v):
            from jax.scipy.special import gammaln

            n, p = self.total_count, jnp.clip(self.probs, 1e-7, 1 - 1e-7)
            coeff = gammaln(n + 1) - gammaln(v + 1) - gammaln(n - v + 1)
            return coeff + v * jnp.log(p) + (n - v) * jnp.log1p(-p)

        return apply_op(f, "binomial_log_prob", value)

    def entropy(self):
        # exact: -sum over the support (total_count must be uniform)
        n = int(np.max(np.asarray(self.total_count)))
        ks = jnp.arange(n + 1, dtype=jnp.float32)
        shape = (n + 1,) + tuple(1 for _ in self._batch_shape)
        lp = _val(self.log_prob(Tensor(ks.reshape(shape)
                                       * jnp.ones(self._batch_shape))))
        valid = ks.reshape(shape) <= self.total_count
        lp = jnp.where(valid, lp, -jnp.inf)
        return Tensor(-jnp.sum(jnp.exp(lp) * jnp.where(valid, lp, 0.0), 0))


class ContinuousBernoulli(Distribution):
    """Reference: distribution/continuous_bernoulli.py (matches torch)."""

    _LIMS = (0.499, 0.501)

    def __init__(self, probs, lims=(0.499, 0.501), name=None):
        self.probs = _val(probs)
        self._LIMS = tuple(lims)
        super().__init__(self.probs.shape)

    def _stable(self):
        return (self.probs < self._LIMS[0]) | (self.probs > self._LIMS[1])

    def _cut(self):
        return jnp.where(self._stable(), self.probs,
                         jnp.full_like(self.probs, self._LIMS[0]))

    def _log_norm(self):
        cut = self._cut()
        log_norm = (jnp.log(jnp.abs(jnp.arctanh(1 - 2 * cut)))
                    - jnp.log(jnp.abs(1 - 2 * cut)) + math.log(2.0))
        x = jnp.square(self.probs - 0.5)
        taylor = math.log(2.0) + (4.0 / 3.0 + 104.0 / 45.0 * x) * x
        return jnp.where(self._stable(), log_norm, taylor)

    @property
    def mean(self):
        cut = self._cut()
        mus = cut / (2 * cut - 1) + 1 / (jnp.log1p(-cut) - jnp.log(cut))
        x = self.probs - 0.5
        taylor = 0.5 + (1.0 / 3.0 + 16.0 / 45.0 * jnp.square(x)) * x
        return Tensor(jnp.where(self._stable(), mus, taylor))

    @property
    def variance(self):
        cut = self._cut()
        vars_ = (cut * (cut - 1) / jnp.square(1 - 2 * cut)
                 + 1 / jnp.square(jnp.log1p(-cut) - jnp.log(cut)))
        x = jnp.square(self.probs - 0.5)
        taylor = 1.0 / 12.0 - (1.0 / 15.0 - 128.0 / 945.0 * x) * x
        return Tensor(jnp.where(self._stable(), vars_, taylor))

    def sample(self, shape=()):
        shape = tuple(shape) + self._batch_shape
        u = jax.random.uniform(_rng.next_key(), shape)
        return Tensor(self._icdf(u))

    def rsample(self, shape=()):
        return self.sample(shape)

    def _icdf(self, u):
        cut = self._cut()
        num = jnp.log1p(-cut + u * (2 * cut - 1)) - jnp.log1p(-cut)
        den = jnp.log(cut) - jnp.log1p(-cut)
        return jnp.where(self._stable(), num / den, u)

    def log_prob(self, value):
        def f(v):
            p = jnp.clip(self.probs, 1e-7, 1 - 1e-7)
            return (v * jnp.log(p) + (1 - v) * jnp.log1p(-p)
                    + self._log_norm())

        return apply_op(f, "continuous_bernoulli_log_prob", value)

    def cdf(self, value):
        def f(v):
            cut = self._cut()
            unbounded = ((jnp.power(cut, v) * jnp.power(1 - cut, 1 - v)
                          + cut - 1) / (2 * cut - 1))
            cdfs = jnp.where(self._stable(), unbounded, v)
            return jnp.clip(cdfs, 0.0, 1.0)

        return apply_op(f, "continuous_bernoulli_cdf", value)

    def entropy(self):
        log_p = jnp.log(jnp.clip(self.probs, 1e-7, 1 - 1e-7))
        log_1mp = jnp.log1p(-jnp.clip(self.probs, 1e-7, 1 - 1e-7))
        mu = _val(self.mean)
        return Tensor(-(mu * log_p + (1 - mu) * log_1mp) - self._log_norm())


def _mvlgamma(a, p):
    """Multivariate log-gamma: log Γ_p(a)."""
    from jax.scipy.special import gammaln

    i = jnp.arange(1, p + 1, dtype=jnp.float32)
    return (p * (p - 1) / 4.0 * math.log(math.pi)
            + gammaln(a[..., None] + (1.0 - i) / 2.0).sum(-1))


class LKJCholesky(Distribution):
    """LKJ prior over Cholesky factors of correlation matrices.

    Reference: distribution/lkj_cholesky.py (onion + cvine sampling)."""

    def __init__(self, dim, concentration=1.0, sample_method="onion",
                 name=None):
        if dim < 2:
            raise ValueError("dim must be >= 2")
        self.dim = int(dim)
        self.concentration = _val(concentration)
        if sample_method not in ("onion", "cvine"):
            raise ValueError(f"unknown sample_method {sample_method}")
        self.sample_method = sample_method
        super().__init__(self.concentration.shape, (self.dim, self.dim))
        # marginal beta parameters for the onion construction
        marginal_conc = self.concentration + 0.5 * (self.dim - 2)
        offset = jnp.concatenate(
            [jnp.zeros(1), jnp.arange(self.dim - 1, dtype=jnp.float32)])
        self._beta = Beta(offset + 0.5, marginal_conc[..., None] - 0.5 * offset)

    def sample(self, shape=()):
        if self.sample_method == "onion":
            w = self._onion(tuple(shape))
        else:
            w = self._cvine(tuple(shape))
        return Tensor(w)

    def _onion(self, shape):
        y = _val(self._beta.sample(shape))[..., None]
        full = shape + self._batch_shape + (self.dim, self.dim)
        u_normal = jnp.tril(
            jax.random.normal(_rng.next_key(), full), -1)
        norm = jnp.linalg.norm(u_normal, axis=-1, keepdims=True)
        u_hyper = u_normal / jnp.where(norm == 0, 1.0, norm)
        w = jnp.sqrt(y) * u_hyper
        diag = jnp.sqrt(jnp.clip(1 - jnp.sum(jnp.square(w), -1),
                                 jnp.finfo(w.dtype).tiny))
        return w + diag[..., None] * jnp.eye(self.dim, dtype=w.dtype)

    def _cvine(self, shape):
        # partial correlations z_ij ~ 2 Beta(b_j, b_j) - 1 with
        # b_j = concentration + (dim - 2 - j)/2, then the standard
        # partial-correlation -> cholesky map:
        #   L[i,j] = z[i,j] * prod_{k<j} sqrt(1 - z[i,k]^2),  L[i,i] = prod_{k<i} ...
        full = shape + self._batch_shape + (self.dim, self.dim)
        col = jnp.arange(self.dim, dtype=jnp.float32)
        bc = self.concentration[..., None] + 0.5 * (self.dim - 2 - col)
        bc = jnp.broadcast_to(jnp.clip(bc, 0.5)[..., None, :], full)
        u = jax.random.beta(_rng.next_key(), bc, bc)
        z = jnp.tril(2 * u - 1, -1)  # strictly-lower partials in (-1, 1)
        tiny = jnp.finfo(u.dtype).tiny
        s = jnp.sqrt(jnp.clip(1 - jnp.square(z), tiny))
        lower = jnp.tril(jnp.ones((self.dim, self.dim), bool), -1)
        cum = jnp.cumprod(jnp.where(lower, s, 1.0), axis=-1)
        excl = jnp.concatenate(
            [jnp.ones(cum.shape[:-1] + (1,)), cum[..., :-1]], -1)
        diag = jnp.diagonal(excl, axis1=-2, axis2=-1)
        return z * excl + diag[..., :, None] * jnp.eye(self.dim)

    def log_prob(self, value):
        def f(v):
            from jax.scipy.special import gammaln

            diag = jnp.diagonal(v, axis1=-2, axis2=-1)[..., 1:]
            order = jnp.arange(2, self.dim + 1, dtype=jnp.float32)
            order = (2 * (self.concentration - 1)[..., None]
                     + self.dim - order)
            unnorm = (order * jnp.log(diag)).sum(-1)
            dm1 = self.dim - 1
            alpha = self.concentration + 0.5 * dm1
            denom = gammaln(alpha) * dm1
            numer = _mvlgamma(alpha - 0.5, dm1)
            pi_const = 0.5 * dm1 * math.log(math.pi)
            return unnorm - (pi_const + numer - denom)

        return apply_op(f, "lkj_log_prob", value)


# ---------------------------------------------------------------- extra KLs
from . import Bernoulli, Categorical, Dirichlet  # noqa: E402


@register_kl(Bernoulli, Bernoulli)
def _kl_bernoulli(p, q):
    a = jnp.clip(p.probs_v, 1e-7, 1 - 1e-7)
    b = jnp.clip(q.probs_v, 1e-7, 1 - 1e-7)
    return Tensor(a * (jnp.log(a) - jnp.log(b))
                  + (1 - a) * (jnp.log1p(-a) - jnp.log1p(-b)))


@register_kl(Categorical, Categorical)
def _kl_categorical(p, q):
    lp = jax.nn.log_softmax(p.logits, -1)
    lq = jax.nn.log_softmax(q.logits, -1)
    return Tensor((jnp.exp(lp) * (lp - lq)).sum(-1))


@register_kl(Beta, Beta)
def _kl_beta(p, q):
    from jax.scipy.special import betaln, digamma

    a1, b1, a2, b2 = p.alpha, p.beta, q.alpha, q.beta
    s1 = a1 + b1
    return Tensor(betaln(a2, b2) - betaln(a1, b1)
                  + (a1 - a2) * digamma(a1) + (b1 - b2) * digamma(b1)
                  + (a2 - a1 + b2 - b1) * digamma(s1))


@register_kl(Gamma, Gamma)
def _kl_gamma(p, q):
    from jax.scipy.special import digamma, gammaln

    a1, r1, a2, r2 = p.concentration, p.rate, q.concentration, q.rate
    return Tensor((a1 - a2) * digamma(a1) - gammaln(a1) + gammaln(a2)
                  + a2 * (jnp.log(r1) - jnp.log(r2)) + a1 * (r2 - r1) / r1)


@register_kl(Dirichlet, Dirichlet)
def _kl_dirichlet(p, q):
    from jax.scipy.special import digamma, gammaln

    a, b = p.concentration, q.concentration
    sa = a.sum(-1)
    return Tensor(gammaln(sa) - gammaln(b.sum(-1))
                  - (gammaln(a) - gammaln(b)).sum(-1)
                  + ((a - b) * (digamma(a) - digamma(sa)[..., None])).sum(-1))


@register_kl(Independent, Independent)
def _kl_independent(p, q):
    if p.reinterpreted_batch_rank != q.reinterpreted_batch_rank:
        raise NotImplementedError
    from . import kl_divergence

    inner = kl_divergence(p.base, q.base)
    return Tensor(_sum_rightmost(_val(inner), p.reinterpreted_batch_rank))
